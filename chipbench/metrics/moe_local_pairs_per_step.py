"""Layer: model step. Pairs (token, held expert) the expert layers of this
rank computed a step, summed over the layers: the program's own count
(`moe.local_pairs` over `moe.steps`: `Program.mark_counter`, read back by
`Executor.run` with the fetches), over every step since the process
started. A count, so the CPU rehearsal reports it too; nothing where the
program has no such counter."""


def read(facts, name):
    try:
        from paddle_tpu import telemetry
        snap = telemetry.snapshot()
        return snap["moe.local_pairs"] / snap["moe.steps"]
    except (ImportError, KeyError, ZeroDivisionError):
        return None
