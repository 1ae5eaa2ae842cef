#!/usr/bin/env python3
"""Compile a cell's programs at its real sizes for a described TPU v5e, with
no chip attached, and print what each would hold in HBM:

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py --workload <name> [--config KEY=VALUE ...]

It compiles the timed block as the window drives it (state donated) and
the reference's local and communication steps.  What the chip's compiler
refuses here (a program over HBM, an unaligned kernel) costs no chip time.
Nothing runs: it says nothing about times or results.
"""
import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--no-reference", action="store_true")
    ap.add_argument("--config", action="append", default=[], metavar="KEY=VALUE",
                    help="change a key of the configuration (to size a cut)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import registry
    from repro.core.driver import sample_block

    jax.config.update("jax_enable_compilation_cache", False)
    one = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    cell = registry.load_cell(ROOT, args.workload)
    for kv in args.config:
        k, v = kv.split("=", 1)
        cell.config[k] = json.loads(v)
    runner, t = cell.runner, cell.traffic
    prog = runner.build(cell, 0)
    params = cell.family.program_shapes(cell.config, prog.dtype)
    x0 = jax.tree.map(lambda s: jax.ShapeDtypeStruct((t["agents"],) + s.shape, s.dtype), params)
    comm0 = jax.eval_shape(lambda: prog.sampler(-1)[1])
    local, comm = jax.eval_shape(lambda: sample_block(prog.sampler, 0, t["block_rounds"]))
    state = jax.eval_shape(lambda x, c: prog.bound.init(prog.loss, x, c), x0, comm0)

    def report(what, lowered):
        try:
            ma = lowered.compile().memory_analysis()
        except Exception as e:  # the compiler's refusal is the finding
            print(f"{what}: REFUSED {str(e).splitlines()[0][:300]}", flush=True)
            return
        print(f"{what}: peak {ma.peak_memory_in_bytes / 2**30:.2f} GiB, arguments "
              f"{ma.argument_size_in_bytes / 2**30:.2f} GiB, aliased "
              f"{ma.alias_size_in_bytes / 2**30:.2f} GiB", flush=True)

    flags = jax.ShapeDtypeStruct((t["block_rounds"],), jnp.bool_)
    report(f"{cell.name} block ({prog.dtype}, {args.config})",
           prog.block_fn.lower(on_chip(state), on_chip(flags), on_chip(local), on_chip(comm)))
    if args.no_reference:
        return 0
    f32 = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.float32 if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype), tree)
    algo = cell.module("reference", t["algo"])
    from functools import partial

    start, local_step, comm_step = algo.make_steps(
        partial(cell.reference.loss, cfg=cell.config), n_agents=t["agents"], t_o=t["t_o"],
        eta_l=t["eta_l"], eta_c=t["eta_c"], topology=t["topology"],
        agent_chunk=cell.reference.AGENT_CHUNK)
    xs = f32(x0)
    step_batch = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape[2:], s.dtype), local)
    with jax.default_matmul_precision("highest"):
        report("reference local step",
               local_step.lower(on_chip(xs), on_chip(xs), on_chip(xs), on_chip(f32(step_batch))))
        report("reference server step",
               comm_step.lower(None, on_chip(xs), on_chip(xs), on_chip(xs),
                               on_chip(f32(comm0)), True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
