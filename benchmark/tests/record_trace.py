"""Record the small profiler trace that `test_trace_reduce.py` checks
`benchmark/trace_reduce.py` against. Run on the chip, by hand:

    python benchmark/tests/record_trace.py <outdir>

It traces three `bench.unit`s inside one `bench.window`; each unit sleeps
on the host under a `host.prepare` annotation (a device-idle gap with a
known name), then runs one jitted step to completion. The `.xplane.pb`
and the profiler's own Perfetto JSON of the same capture are copied to
<outdir>; the test reads the first with trace_reduce and the second with
a plain JSON parse, and the two have to agree.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main(outdir: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x) + 1.0)
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()  # compile outside the capture
    tmp = tempfile.mkdtemp(prefix="trace-fixture-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, create_perfetto_trace=True, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.unit"):
                with jax.profiler.TraceAnnotation("host.prepare"):
                    time.sleep(0.05)
                with jax.profiler.TraceAnnotation("device.step"):
                    for _ in range(4):
                        x = f(x)
                    x.block_until_ready()
    jax.profiler.stop_trace()
    os.makedirs(outdir, exist_ok=True)
    for pattern, name in (("*.xplane.pb", "fixture.xplane.pb"),
                          ("*perfetto_trace.json.gz", "fixture.perfetto.json.gz")):
        (path,) = glob.glob(os.path.join(tmp, "**", pattern), recursive=True)
        shutil.copy(path, os.path.join(outdir, name))
    shutil.rmtree(tmp)
    print(f"device: {jax.devices()[0].device_kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
