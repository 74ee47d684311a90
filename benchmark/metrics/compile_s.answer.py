"""Compile seconds per answer: the window's delta of the `jit.trace_s`,
`jit.lower_s` and `jit.compile_s` histogram totals (work seconds, every
thread), over the answers. 0.0 when none fired; None when the program
keeps no such histograms."""

NAMES = ("jit.trace_s", "jit.lower_s", "jit.compile_s")


def read(r):
    hists = [r.counters[n] for n in NAMES if isinstance(r.counters.get(n), dict)]
    if not r.units or not hists:
        return None
    return sum(h["total"] for h in hists) / len(r.units)
