"""Compile seconds before the window (set-up, the warm-up answer
included): the process totals of the `jit.trace_s`, `jit.lower_s` and
`jit.compile_s` histograms less their delta over the window, which is all
the reading's counters hold. None when the program keeps none."""

NAMES = ("jit.trace_s", "jit.lower_s", "jit.compile_s")


def read(r):
    from simtpu.obs.metrics import REGISTRY

    total, seen = 0.0, False
    for n in NAMES:
        now = REGISTRY.value(n, default=None)
        if not isinstance(now, dict):
            continue
        seen = True
        window = r.counters.get(n)
        total += now["total"] - (window["total"] if isinstance(window, dict) else 0.0)
    return total if seen else None
