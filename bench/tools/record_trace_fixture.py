"""Record the small device trace that tests/bench/test_bench_trace.py reads.

    python bench/tools/record_trace_fixture.py --out tests/bench/data

Run it on a TPU.  It traces three host spans named as the harness names
them (``kernel <sig>`` around a jitted call that ends in
``block_until_ready``, ``skip bookkeeping`` around a 2 ms host sleep,
all inside ``bench window``) three times, and
writes the profiler's ``.xplane.pb`` as ``<out>/fixture.xplane.pb``, with
a summary of every plane, line and the first events of each to stdout.
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 1
    print("device", dev.platform, dev.device_kind, len(jax.devices()))

    mm = jax.jit(lambda a, b: jnp.tanh(a @ b))
    ew = jax.jit(lambda a: jnp.sum(jnp.exp(a) * 2.0))
    a = jnp.ones((2048, 2048), jnp.float32)
    mm(a, a).block_until_ready()
    ew(a).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("kernel mm(2048)"):
                mm(a, a).block_until_ready()
            with jax.profiler.TraceAnnotation("skip bookkeeping"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("kernel ew(2048)"):
                ew(a).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "fixture.xplane.pb")
    shutil.copy(path, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print("wrote", dst, os.path.getsize(dst))
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(dst)
    for plane in pd.planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), len(lines))
        for line in lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for e in evs[:6]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns)
    return 0


if __name__ == "__main__":
    sys.exit(main())
