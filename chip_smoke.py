#!/usr/bin/env python3
"""Chip smoke test: the main paths of this repo, end to end, on one TPU.

    python3 chip_smoke.py                 # one chip (what CI on the chip runs)
    python3 chip_smoke.py --chips 4       # only the collective path, 4 chips
    JAX_PLATFORMS=cpu python3 chip_smoke.py --reduced    # CPU rehearsal

Default run, in this one process (a chip belongs to one process at a time):

1. **train** — ``repro.launch.train.main`` on mamba2-370m at its published
   width and depth: PISCO, 2 agents on a ring, ``T_o = 2``, scan driver,
   batch 2 x 1024 tokens, a few rounds under a seed whose Bernoulli(p) draws
   give both gossip (W) and server (J) rounds.  Every logged loss must be
   finite and both round kinds must occur.
2. **reference** — agent 0's loss at the initial point ``X^0`` on its first
   batch ``Z^0`` (the forward PISCO's line 2 runs before round 0): the chip's
   bf16 forward against the same forward in float32 on the host CPU.
3. **serve** — ``repro.launch.serve.main`` on the same architecture at full
   width: a synthetic 4-agent personalized fleet, 4 requests, measured step
   costs; run cold (compiles) and again warm.  Every request must come back
   with tokens.

``--chips 4`` runs only the agent-sharded collective path: four agents, one
per chip, mamba2-370m at full width.  Ring gossip (``ppermute``) and server
averaging (``psum``) from :mod:`repro.core.mixing` against the single-device
mixers (edge-list gossip with the mesh ring's weights, ``tree_agent_mean``)
on the same sharded inputs, for the full-depth parameter tree and for one
PISCO round of each kind on the model cut to two layers.

The script refuses to run unless JAX's first device is a TPU (``--reduced``
is the CPU rehearsal at the smoke-size presets).  Earlier lines report
device, compile/steady seconds, peak device memory, losses and tokens/s;
the last line of stdout is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
OUT = ROOT / "chiprun_out" / "chip_smoke"

ARCH = "mamba2-370m"
TRAIN_ROUNDS = 4
# p = 0.5, seed 0 draws W J J J for rounds 0..3: both round kinds occur
TRAIN_P, TRAIN_SEED = 0.5, 0
# |chip bf16 - CPU f32| on the same forward, relative to the reference loss
FORWARD_RTOL = 1e-2
# round 0's logged loss (3 forwards after 0..2 SGD steps, both agents) may
# sit this far from agent 0's loss at X^0
ROUND0_RTOL = 5e-2
# gossip/server mixes are linear with exact weights: agreement to one bf16
# rounding of the largest entry
MIX_RTOL = 2.0 ** -7
# one PISCO round through two different programs: bf16 gradients may round
# differently, the state may not move further than this
ROUND_RTOL = 2e-2


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


class _Tee(io.TextIOBase):
    """stdout pass-through that keeps each printed line with its time."""

    def __init__(self, out):
        self.out, self.buf, self.lines = out, "", []

    def write(self, s: str) -> int:
        self.out.write(s)
        self.buf += s
        while "\n" in self.buf:
            line, self.buf = self.buf.split("\n", 1)
            self.lines.append((time.perf_counter(), line))
        return len(s)

    def flush(self) -> None:
        self.out.flush()


def run_main(main, argv):
    """Call an entry point's ``main(argv)`` in-process; return
    ``(timed lines, wall seconds, compile seconds)``."""
    from repro.obs import track_compile_time

    tee = _Tee(sys.stdout)
    t0 = time.perf_counter()
    with track_compile_time() as cstats, contextlib.redirect_stdout(tee):
        rc = main(argv)
    if rc != 0:
        fail(f"{main.__module__}.main returned {rc}")
    return [(t - t0, line) for t, line in tee.lines], \
        time.perf_counter() - t0, cstats.seconds


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# one chip: train, reference, serve
# ---------------------------------------------------------------------------


def train_phase(reduced: bool, dev) -> dict:
    from repro.launch import train

    argv = [
        "--arch", ARCH, "--n-agents", "2", "--t-o", "2", "--driver", "scan",
        "--rounds", str(TRAIN_ROUNDS), "--p", str(TRAIN_P),
        "--seed", str(TRAIN_SEED), "--log-every", "1",
    ]
    argv += ["--reduced", "--seq", "64", "--batch", "2"] if reduced else \
        ["--seq", "1024", "--batch", "2"]
    print(f"[train] repro.launch.train {' '.join(argv)}", flush=True)
    lines, wall, compile_s = run_main(train.main, argv)
    rounds = {}
    for t, line in lines:
        m = re.match(r"round\s+(\d+) \[([WJ])\] loss=(\S+)", line)
        if m:
            rounds[int(m.group(1))] = (t, m.group(2), float(m.group(3)))
    if sorted(rounds) != list(range(TRAIN_ROUNDS)):
        fail(f"train logged rounds {sorted(rounds)}, want 0..{TRAIN_ROUNDS - 1}")
    losses = [rounds[k][2] for k in range(TRAIN_ROUNDS)]
    kinds = "".join(rounds[k][1] for k in range(TRAIN_ROUNDS))
    if not all(math.isfinite(v) for v in losses):
        fail(f"non-finite training loss: {losses}")
    if "W" not in kinds or "J" not in kinds:
        fail(f"round kinds {kinds}: need a gossip (W) and a server (J) round")
    first = rounds[0][0]
    steady = (rounds[TRAIN_ROUNDS - 1][0] - first) / (TRAIN_ROUNDS - 1)
    out = {
        "losses": losses, "kinds": kinds, "wall_s": wall,
        "compile_s": compile_s, "first_round_s": first,
        "steady_round_s": steady, "peak_bytes": peak_bytes(dev),
    }
    print(f"[train] kinds={kinds} losses={losses}")
    print(f"[train] wall {wall:.2f} s, compile {compile_s:.2f} s, "
          f"round 0 done at {first:.2f} s (init + compile + run), "
          f"steady {steady:.3f} s/round, peak {out['peak_bytes']} B", flush=True)
    return out


def reference_phase(reduced: bool, round0_loss: float) -> dict:
    """Agent 0's loss at X^0 on Z^0: chip (bf16, the training dtype) vs the
    host CPU in float32, from the launcher's own init and sampler."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config, get_reduced
    from repro.launch.train import make_lm_sampler
    from repro.models import get_bundle

    cfg = get_reduced(ARCH) if reduced else get_config(ARCH)
    seq = 64 if reduced else 1024
    bundle = get_bundle(cfg)
    params = bundle.init(jax.random.PRNGKey(TRAIN_SEED))
    # the launcher draws Z^0 first: sampler(-1) is the batch PISCO's init sees
    _, comm0 = make_lm_sampler(cfg, 2, 2, seq, 2, TRAIN_SEED)(-1)
    tokens = comm0["tokens"][0]
    t0 = time.perf_counter()
    chip = float(jax.jit(bundle.loss)(params, {"tokens": tokens}))
    chip_s = time.perf_counter() - t0

    cpu = jax.devices("cpu")[0]
    host = jax.tree.map(lambda a: np.asarray(a, np.float32), jax.device_get(params))
    del params
    bundle32 = get_bundle(dataclasses.replace(cfg, dtype="float32"))
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(bundle32.loss)(
            jax.device_put(host, cpu),
            {"tokens": jax.device_put(np.asarray(tokens), cpu)},
        ))
    ref_s = time.perf_counter() - t0
    err = abs(chip - ref) / abs(ref)
    drift = abs(round0_loss - ref) / abs(ref)
    print(f"[reference] agent 0 loss at X^0: chip {chip:.6f}, cpu f32 {ref:.6f}, "
          f"rel err {err:.2e} (tol {FORWARD_RTOL:g}); round-0 loss "
          f"{round0_loss:.6f} is {drift:.2e} away (tol {ROUND0_RTOL:g}); "
          f"chip {chip_s:.2f} s, cpu {ref_s:.2f} s", flush=True)
    if not (math.isfinite(chip) and err <= FORWARD_RTOL):
        fail(f"chip forward {chip} vs CPU float32 {ref}: rel err {err:.3e}")
    if drift > ROUND0_RTOL:
        fail(f"round-0 loss {round0_loss} vs reference {ref}: {drift:.3e}")
    return {"chip_loss": chip, "cpu_f32_loss": ref, "rel_err": err,
            "round0_drift": drift}


def serve_phase(reduced: bool, dev) -> dict:
    from repro.launch import serve
    from repro.obs import read_jsonl

    n_requests = 4
    OUT.mkdir(parents=True, exist_ok=True)
    out = {}
    for label in ("cold", "warm"):
        metrics = OUT / f"serve_{label}.jsonl"
        metrics.unlink(missing_ok=True)
        argv = [
            "--arch", ARCH, "--agents", "4", "--slots", "2",
            "--requests", str(n_requests), "--prompt-len", "32", "--gen", "8",
            "--arrival", "poisson:rate=4", "--seed", "0",
            "--metrics-out", str(metrics),
        ] + (["--reduced"] if reduced else [])
        print(f"[serve/{label}] repro.launch.serve {' '.join(argv)}", flush=True)
        _, wall, compile_s = run_main(serve.main, argv)
        m = read_jsonl(str(metrics))[-1]["metrics"]
        per_req = m["serve.request_tokens"]
        if m["serve.requests"]["value"] != n_requests or \
                per_req["count"] != n_requests or per_req["min"] < 1:
            fail(f"serve answered {m['serve.requests']['value']}/{n_requests} "
                 f"requests, fewest tokens {per_req.get('min')}")
        out[label] = {
            "wall_s": wall, "compile_s": compile_s,
            "tokens": m["serve.tokens"]["value"],
            "tokens_per_s": m["serve.tokens_per_s"]["value"],
            "p50_s": m["serve.p50_s"]["value"],
        }
        print(f"[serve/{label}] wall {wall:.2f} s, compile {compile_s:.2f} s, "
              f"{out[label]['tokens']} tokens, "
              f"{out[label]['tokens_per_s']:.2f} tok/s (measured step costs)",
              flush=True)
    out["peak_bytes"] = peak_bytes(dev)
    print(f"[serve] process peak {out['peak_bytes']} B", flush=True)
    return out


# ---------------------------------------------------------------------------
# four chips: collective mixers vs single-device references
# ---------------------------------------------------------------------------


def _rel_err(a, b) -> float:
    import jax
    import numpy as np

    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.float32)
        scale = max(float(np.max(np.abs(y))), 1e-30)
        worst = max(worst, float(np.max(np.abs(x - y))) / scale)
    return worst


def _check_one_agent_per_device(tree, n: int, what: str) -> None:
    import jax

    for leaf in jax.tree.leaves(tree):
        shards = leaf.addressable_shards
        devs = {s.device.id for s in shards}
        rows = sorted(s.index[0].start or 0 for s in shards)
        if len(devs) != n or rows != list(range(n)) or \
                any(s.data.shape[0] != 1 for s in shards):
            fail(f"{what}: agent axis not one agent per device "
                 f"(devices {sorted(devs)}, rows {rows})")


def collective_phase(reduced: bool, n: int) -> dict:
    import dataclasses

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, get_reduced
    from repro.core.mixing import MixingOps, collective_shift_mixing, sparse_gossip
    from repro.core.pisco import PiscoConfig, init_state, make_round_fn
    from repro.core.topology import make_sparse_topology
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import gossip_matrix, mesh_gossip_shifts
    from repro.models import get_bundle
    from repro.utils.pytree import tree_agent_mean

    mesh = make_mesh((n,), ("agents",))
    axes = ("agents",)
    shifts = mesh_gossip_shifts(mesh, axes)
    w = gossip_matrix(mesh, axes, shifts)
    print(f"[collective] {n} agents on {n} devices, ring W row 0 = {w[0].tolist()}")
    # the mesh ring's W as edge weights over the ring's edge list
    ring = make_sparse_topology("ring", n)
    _, edge_mix = sparse_gossip(ring)
    senders = np.concatenate([ring.edges[:, 0], ring.edges[:, 1]])
    receivers = np.concatenate([ring.edges[:, 1], ring.edges[:, 0]])
    edge_w = np.asarray(w[receivers, senders], np.float32)
    self_w = np.asarray(np.diag(w), np.float32)

    def ring_gossip(tree):
        return edge_mix(tree, edge_w, self_w)

    def agent_sharded(bundle, keys):
        sds = jax.eval_shape(jax.vmap(bundle.init), keys)
        spec = jax.tree.map(lambda _: P("agents"), sds)
        shard = jax.tree.map(lambda s: NamedSharding(mesh, s), spec)
        return jax.jit(jax.vmap(bundle.init), out_shardings=shard)(keys), spec

    out = {}
    # (a) the mixers alone on the full-depth parameter tree, four distinct
    # agent models (different init keys), one per chip
    cfg = get_reduced(ARCH) if reduced else get_config(ARCH)
    bundle = get_bundle(cfg)
    keys = jax.random.split(jax.random.PRNGKey(1), n)
    x, spec = agent_sharded(bundle, keys)
    _check_one_agent_per_device(x, n, "input")
    ops = collective_shift_mixing(mesh, axes, spec, shifts)
    t0 = time.perf_counter()
    mixed = jax.jit(ops.gossip)(x)
    _check_one_agent_per_device(mixed, n, "collective gossip output")
    err_w = _rel_err(mixed, jax.jit(ring_gossip)(x))
    avg = jax.jit(ops.global_avg)(x)
    _check_one_agent_per_device(avg, n, "collective server output")
    err_j = _rel_err(avg, jax.jit(tree_agent_mean)(x))
    print(f"[collective] full-depth tree: gossip rel err {err_w:.2e}, server "
          f"rel err {err_j:.2e} (tol {MIX_RTOL:g}), "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    if not (err_w <= MIX_RTOL and err_j <= MIX_RTOL):
        fail(f"collective mixers disagree with edge-list: W {err_w}, J {err_j}")
    out.update(mix_gossip_rel_err=err_w, mix_server_rel_err=err_j)
    del x, mixed, avg

    # (b) one PISCO round of each kind on the full-width model cut to two
    # layers: collective mixers vs edge-list mixing of the same sharded state
    cfg2 = dataclasses.replace(cfg, n_layers=2)
    bundle2 = get_bundle(cfg2)
    x2, spec2 = agent_sharded(bundle2, keys)
    seq = 64 if reduced else 256
    rng = np.random.default_rng(0)
    tok_shard = NamedSharding(mesh, P(None, "agents"))
    local = {"tokens": jax.device_put(
        rng.integers(0, cfg.vocab_size, (1, n, 1, seq), dtype=np.int32), tok_shard)}
    comm = {"tokens": jax.device_put(
        rng.integers(0, cfg.vocab_size, (n, 1, seq), dtype=np.int32),
        NamedSharding(mesh, P("agents")))}
    pcfg = PiscoConfig(n_agents=n, t_o=1, eta_l=0.05, p=0.5)
    coll = collective_shift_mixing(mesh, axes, spec2, shifts)
    single = MixingOps(gossip=ring_gossip, global_avg=tree_agent_mean)
    state = jax.jit(lambda x0, b0: init_state(bundle2.loss, x0, b0))(x2, comm)
    for kind, is_global in (("gossip", False), ("server", True)):
        t0 = time.perf_counter()
        s_c, m_c = jax.jit(make_round_fn(
            bundle2.loss, pcfg, coll, global_round=is_global))(state, local, comm)
        s_d, m_d = jax.jit(make_round_fn(
            bundle2.loss, pcfg, single, global_round=is_global))(state, local, comm)
        _check_one_agent_per_device(s_c.x, n, f"{kind} round state")
        err = max(_rel_err(getattr(s_c, f), getattr(s_d, f)) for f in "xyg")
        lc, ld = float(m_c.loss), float(m_d.loss)
        print(f"[collective] PISCO {kind} round: state rel err {err:.2e} "
              f"(tol {ROUND_RTOL:g}), loss {lc:.6f} vs edge-list {ld:.6f}, "
              f"{time.perf_counter() - t0:.2f} s", flush=True)
        if not (math.isfinite(lc) and err <= ROUND_RTOL):
            fail(f"collective {kind} round disagrees with edge-list: {err}")
        out[f"round_{kind}_rel_err"] = err
    return out


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the collective path across four chips")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size presets; also runs on the CPU (rehearsal)")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        fail(f"no repro package under {SRC}: run from a checkout of the repo")
    sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.reduced:
        fail(f"JAX's first device is {dev.platform!r} ({dev.device_kind}), "
             "not a TPU; this smoke test does not run elsewhere")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
             f"{len(devices)}")

    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache}", flush=True)

    report = {}
    if args.chips == 4:
        report["collective"] = collective_phase(args.reduced, args.chips)
    else:
        report["train"] = train_phase(args.reduced, dev)
        report["reference"] = reference_phase(
            args.reduced, report["train"]["losses"][0]
        )
        report["serve"] = serve_phase(args.reduced, dev)
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"report_chips{args.chips}.json").write_text(
        json.dumps(report, indent=1, default=float)
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
