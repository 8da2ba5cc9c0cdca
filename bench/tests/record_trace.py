"""Record the small device trace that ``test_trace.py`` reduces.

    python bench/tests/record_trace.py        # on a machine with one TPU

Runs a few steps of a tiny jitted program (a matmul, a loop of a small
Pallas kernel) under the profiler with the benchmark's host annotations,
copies the ``.xplane.pb`` to ``bench/tests/data/one_chip.xplane.pb`` (and,
where the directory ``chiprun_out`` exists at the checkout's root, there too)
and
prints, per plane and line, the event names and their summed durations.
"""
import glob
import os
import shutil
import sys
from collections import defaultdict
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402


def _scale_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0 + 1.0


def scale(x):
    return pl.pallas_call(_scale_kernel, name="bench_scale_kernel",
                          out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


@jax.jit
def step(a, b):
    def body(c, _):
        return scale(c) * 0.5, None
    c, _ = jax.lax.scan(body, a @ b, None, length=4)
    return c, jnp.sum(c)


def main(out=HERE / "data" / "one_chip.xplane.pb"):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"no TPU: JAX sees {dev.platform}")
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    b = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready(step(a, b))
    tmp = HERE.parent / ".traces" / "record"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp))
    with jax.profiler.TraceAnnotation("bench_window"):
        for i in range(3):
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                with jax.profiler.TraceAnnotation("dispatch"):
                    c, s = step(a, b)
                with jax.profiler.TraceAnnotation("sync"):
                    jax.block_until_ready(s)
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True)[0]
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    extra = HERE.parents[1] / "chiprun_out"
    if extra.is_dir():
        shutil.copy(path, extra / out.name)
    print(f"{out}: {out.stat().st_size} bytes")
    describe(out)
    shutil.rmtree(tmp, ignore_errors=True)


def describe(path, limit=25):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            tot = defaultdict(float)
            cnt = defaultdict(int)
            for e in evs:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
            t0 = min((e.start_ns for e in evs), default=0)
            t1 = max((e.start_ns + e.duration_ns for e in evs), default=0)
            print(f"  LINE {line.name!r}: {len(evs)} events, span "
                  f"{t0:.0f}..{t1:.0f}")
            for name, t in sorted(tot.items(), key=lambda kv: -kv[1])[:limit]:
                print(f"    {cnt[name]:5d} x {t / 1e3:12.1f} us  {name[:120]}")
            for e in evs[:2]:
                stats = {}
                for s in e.stats:
                    try:
                        stats[s[0]] = str(s[1])[:160]
                    except Exception:  # noqa: BLE001 - best-effort dump
                        pass
                print(f"    e.g. {e.name[:80]!r} start {e.start_ns} stats {stats}")


if __name__ == "__main__":
    if len(sys.argv) > 1:
        describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25)
    else:
        main()
