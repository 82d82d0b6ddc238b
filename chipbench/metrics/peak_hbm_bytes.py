"""Layer: device. The run's `memory_peak_bytes`, fullest device: the larger
of the allocator's `peak_bytes_in_use` and of `bytes_in_use` at window close
plus `peak_bytes_reserved` (a running program's scratch counts as reserved,
never as in use). Neither statistic alone is the peak, and the sum is not
exact either: chipbench/run.py::memory_peak, PERF.md section 3."""


def read(facts, name):
    return float(facts["memory_peak_bytes"]) or None
