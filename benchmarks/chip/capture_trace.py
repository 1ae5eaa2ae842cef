#!/usr/bin/env python3
"""Record the small chip trace that the trace reduction's test reads:

    python3 benchmarks/chip/capture_trace.py --out <dir>

Inside a ``bench.window`` annotation, five times: dispatch a chain of
matmuls (``bench.dispatch``), wait for it (``bench.sync``), then sleep 20 ms
on the host (``bench.sample``), in which the device has nothing to do.
Writes ``<dir>/trace.xplane.pb`` and ``<dir>/expected.json`` with what the
host measured.
"""
import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

SLEEP_S = 0.02
ROUNDS = 5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from chipbench import device
    from chipbench.trace import Phases, find_xplane, reduce_xplane

    device.require_tpu(1)
    x = jax.random.normal(jax.random.PRNGKey(0), (2048, 2048), jnp.bfloat16)
    chain = jax.jit(lambda a: jax.lax.fori_loop(0, 8, lambda _, b: (b @ a) * 0.01, a))
    chain(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="chipbench-capture-")
    phases = Phases()
    jax.profiler.start_trace(tmp)
    with phases("window"):
        for _ in range(ROUNDS):
            with phases("dispatch"):
                y = chain(x)
            with phases("sync"):
                y.block_until_ready()
            with phases("sample"):
                time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(find_xplane(tmp), out / "trace.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    expected = {k: phases.total(k) for k in phases.seconds}
    expected["reduction"] = reduce_xplane(out / "trace.xplane.pb")
    (out / "expected.json").write_text(json.dumps(expected, indent=1))
    print(json.dumps(expected))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
