import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and extract memory / cost / collective analyses.

MUST be run as its own process (it forces 512 host devices before any other
jax usage):

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun

Each run writes one JSON artifact per (arch, shape, mesh, step) that
benchmarks/roofline.py aggregates into EXPERIMENTS.md §Dry-run / §Roofline.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import (  # noqa: E402
    build_decode_step,
    build_prefill_step,
    build_train_steps,
)
from repro.models import get_bundle  # noqa: E402
from repro.utils.hlo import Roofline, collective_bytes  # noqa: E402

SKIP_LONG_DECODE_NOTE = (
    "long_500k skipped: pure full-attention decode (unbounded KV cache is "
    "not sub-quadratic); see DESIGN.md §4"
)


def applicable(arch: str, shape_name: str) -> bool:
    cfg = get_config(arch)
    if shape_name == "long_500k":
        return cfg.supports_long_decode()
    return True


def run_one(arch: str, shape_name: str, mesh_kind: str, *, t_o: int = 1,
            agent_mode: str = "flat", steps_filter=None,
            wire_dtype: str = "float32", loss_chunk: int = 0,
            remat_policy: str = "full", ssm_chunk: int = 0,
            opt_idle_batch: bool = False) -> list:
    import dataclasses as _dc

    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    cfg = get_config(arch)
    if loss_chunk:
        cfg = _dc.replace(cfg, loss_chunk=loss_chunk)
    if remat_policy != "full":
        cfg = _dc.replace(cfg, remat_policy=remat_policy)
    if ssm_chunk and cfg.ssm is not None:
        cfg = _dc.replace(cfg, ssm=_dc.replace(cfg.ssm, chunk=ssm_chunk))
    bundle = get_bundle(cfg)
    n_chips = mesh.size

    if shape.kind == "train":
        steps = build_train_steps(
            bundle, shape, mesh, t_o=t_o, agent_mode=agent_mode,
            wire_dtype=wire_dtype,
        )
    elif shape.kind == "prefill":
        steps = {"prefill": build_prefill_step(bundle, shape, mesh)}
    else:
        steps = {"decode": build_decode_step(
            bundle, shape, mesh, opt_idle_batch=opt_idle_batch)}

    results = []
    for name, spec in steps.items():
        if steps_filter and name not in steps_filter:
            continue
        rec = {
            "arch": arch,
            "shape": shape_name,
            "mesh": mesh_kind,
            "n_chips": n_chips,
            "step": name,
            "agent_mode": agent_mode,
            "t_o": t_o,
            "variant": {
                "wire_dtype": wire_dtype, "loss_chunk": loss_chunk,
                "remat_policy": remat_policy, "ssm_chunk": ssm_chunk,
                "opt_idle_batch": opt_idle_batch,
            },
            "notes": _json_safe(spec.notes),
        }
        t0 = time.perf_counter()
        try:
            lowered = spec.lower()
            rec["lower_s"] = time.perf_counter() - t0
            t1 = time.perf_counter()
            compiled = lowered.compile()
            rec["compile_s"] = time.perf_counter() - t1

            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "peak_bytes": int(ma.peak_memory_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
            }
            ca = compiled.cost_analysis() or {}
            rec["cost"] = {
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
                "transcendentals": float(ca.get("transcendentals", 0.0)),
            }
            hlo = compiled.as_text()
            rec["collectives"] = collective_bytes(hlo)
            rec["hlo_lines"] = hlo.count("\n")

            model_flops = _model_flops(cfg, shape, name, t_o)
            roof = Roofline.from_counts(
                rec["cost"]["flops"],
                rec["cost"]["bytes_accessed"],
                float(rec["collectives"]["total"]),
                model_flops=model_flops,
                n_chips=n_chips,
            )
            rec["roofline"] = roof.to_dict()
            rec["status"] = "ok"
        except Exception as e:  # noqa: BLE001 — record the failure, keep going
            rec["status"] = "error"
            rec["error"] = f"{type(e).__name__}: {e}"
            rec["traceback"] = traceback.format_exc()[-4000:]
        results.append(rec)
    return results


def _json_safe(obj):
    if isinstance(obj, dict):
        return {str(k): _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if hasattr(obj, "item"):
        return obj.item()
    return obj


def _model_flops(cfg, shape, step_name: str, t_o: int) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE), whole step.

    Train rounds run t_o + 1 gradient evaluations (forward+backward = 3× fwd);
    prefill is one forward (2·N·D); decode is one token (D = batch)."""
    n_active = cfg.active_param_count()
    if step_name.startswith("train"):
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens * (t_o + 1)
    if step_name == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS) + ["qwen3-8b-swa"])
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true", help="run every applicable pair")
    ap.add_argument("--t-o", type=int, default=1)
    ap.add_argument("--agent-mode", choices=["flat", "hierarchical"], default="flat")
    ap.add_argument("--steps", nargs="*", default=None,
                    help="subset of step names (train_gossip train_global ...)")
    ap.add_argument("--wire-dtype", default="float32",
                    choices=["float32", "native"],
                    help="gossip ppermute payload dtype (Perf lever)")
    ap.add_argument("--loss-chunk", type=int, default=0,
                    help=">0: chunked CE loss (Perf lever)")
    ap.add_argument("--remat-policy", default="full", choices=["full", "dots"])
    ap.add_argument("--ssm-chunk", type=int, default=0,
                    help="override SSD chunk length (Perf lever)")
    ap.add_argument("--opt-idle-batch", action="store_true",
                    help="batch-1 decode: seq/expert-shard over the idle data axis")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args(argv)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        pairs = [
            (a, s) for a in ARCH_IDS for s in SHAPES if applicable(a, s)
        ]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        pairs = [(args.arch, args.shape)]

    os.makedirs(args.out, exist_ok=True)
    n_fail = 0
    for arch, shape_name in pairs:
        if not applicable(arch, shape_name):
            print(f"SKIP {arch} x {shape_name}: {SKIP_LONG_DECODE_NOTE}")
            continue
        for mesh_kind in meshes:
            for rec in run_one(
                arch, shape_name, mesh_kind,
                t_o=args.t_o, agent_mode=args.agent_mode,
                steps_filter=args.steps,
                wire_dtype=args.wire_dtype, loss_chunk=args.loss_chunk,
                remat_policy=args.remat_policy, ssm_chunk=args.ssm_chunk,
                opt_idle_batch=args.opt_idle_batch,
            ):
                tag = f"{arch}__{shape_name}__{mesh_kind}__{rec['step']}"
                if args.agent_mode != "flat":
                    tag += f"__{args.agent_mode}"
                if args.tag:
                    tag += f"__{args.tag}"
                path = os.path.join(args.out, tag + ".json")
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(
                        f"OK   {tag}: compile={rec['compile_s']:.1f}s "
                        f"flops/dev={rec['cost']['flops']:.3e} "
                        f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB "
                        f"coll={rec['collectives']['total']/2**20:.1f}MiB "
                        f"dominant={r['dominant']}"
                    )
                    # the dry-run contract: print the full analyses
                    print(f"     memory_analysis: {rec['memory']}")
                    print(f"     cost_analysis:   {rec['cost']}")
                    print(f"     collectives:     {rec['collectives']}")
                else:
                    n_fail += 1
                    print(f"FAIL {tag}: {rec['error']}")
                sys.stdout.flush()
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
