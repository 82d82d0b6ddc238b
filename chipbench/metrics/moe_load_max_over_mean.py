"""Layer: model step. The fullest held expert's pairs over the mean held
expert's, the layers weighted by their pairs: `moe.max_expert_pairs` x
`experts_held` / `moe.local_pairs`, all steps since the process started
(the program's counters, `Program.mark_counter`). 1 is an even load; the
grouped products pad each expert's group to whole tiles, so an uneven load
costs them rows. Nothing where the program has no such counter."""


def read(facts, name):
    try:
        from paddle_tpu import telemetry
        snap = telemetry.snapshot()
        return snap["moe.max_expert_pairs"] * facts["cfg"]["experts_held"] \
            / snap["moe.local_pairs"]
    except (ImportError, KeyError, ZeroDivisionError):
        return None
