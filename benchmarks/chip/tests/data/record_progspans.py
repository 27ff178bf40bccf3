"""Record ``progspans.xplane.pb``: a small CPU profiler trace that holds the
benchmark's spans (a window, set-up, three requests) and the program's own
spans inside and outside them, with their start attributes as stats.

    PYTHONPATH=src JAX_PLATFORMS=cpu python \\
        benchmarks/chip/tests/data/record_progspans.py

Every program span sleeps a known time: set-up and the stretch between two
requests hold spans that a per-request metric must leave out.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def record(out: str) -> None:
    import jax
    from jax.profiler import TraceAnnotation
    from repro import obs
    from repro.obs import jax_bridge
    jax_bridge.install()

    def work(name, ms, **attrs):
        with obs.span(name, **attrs):
            time.sleep(ms / 1e3)

    logdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(logdir, profiler_options=opts), \
            TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.setup"):
            work("lsm.populate", 30, records=1000)
            work("tune.grid", 5, problems=999, starts=2, steps=3)
            work("tune.score", 20, policy="klsm")
            work("engine.read", 20, keys=7)
            work("engine.flush", 20, entries=5)
        for i in range(3):
            with TraceAnnotation("bench.request"):
                with obs.span("tune.select", cells=90):
                    work("tune.score", 2, policy="klsm")
                    work("tune.score", 2, policy="klsm")
                work("tune.grid", 1, problems=15, starts=2, steps=3)
                work("tune.grid", 1, problems=75, starts=2, steps=3)
                work("engine.read", 3, keys=500)
                if i != 1:
                    with obs.span("engine.flush", entries=12):
                        work("engine.compact", 4, kind="spill")
            work("tune.score", 10, policy="klsm")      # between requests
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    shutil.copy(path, out)
    shutil.rmtree(logdir)


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(HERE, "..", "..", "..", "..", "src"))
    record(os.path.join(HERE, "progspans.xplane.pb"))
