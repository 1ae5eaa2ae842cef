"""End-to-end driver: PISCO-train a ~126M-parameter decoder LM for a few
hundred communication rounds on heterogeneous token streams.

This is the deliverable (b) end-to-end example: real model (GQA + SwiGLU,
12 layers, d_model 768, vocab 8192 ~ 126M params), real data pipeline
(per-agent Zipf streams with distinct bigram structure = heterogeneity),
PISCO rounds with a Bernoulli(p) server schedule, checkpointing, and eval.

    PYTHONPATH=src python examples/train_federated_lm.py --rounds 300

On the CPU container a round takes O(10 s); pass --rounds 20 for a smoke run.
The same ModelBundle/step functions drive the production mesh via
repro.launch.{train,dryrun}.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.core import PiscoConfig, make_sparse_topology, replicate_params, sparse_mixing
from repro.core.algorithms import get_algorithm
from repro.core.driver import make_block_fn, predraw_schedule, sample_block
from repro.core.schedule import CommAccountant
from repro.data.synthetic import synthetic_lm_tokens
from repro.models import ModelConfig, config_to_dict, get_bundle

LM_100M = ModelConfig(
    name="pisco-lm-100m",
    arch_type="dense",
    n_layers=12,
    d_model=768,
    n_heads=12,
    n_kv_heads=4,
    head_dim=64,
    d_ff=3072,
    vocab_size=8192,
    mlp_type="swiglu",
    dtype="float32",
    attn_chunk=256,
    remat=False,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=300)
    ap.add_argument("--n-agents", type=int, default=4)
    ap.add_argument("--t-o", type=int, default=1)
    ap.add_argument("--p", type=float, default=0.1)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--eta-l", type=float, default=0.1)
    ap.add_argument("--local-opt", default=None,
                    help="local update rule, e.g. momentum | adam:lr=0.01 "
                         "(default: hardcoded tracked-SGD)")
    ap.add_argument("--server-opt", default=None,
                    help="FedOpt server rule, e.g. fedavgm | fedadam")
    ap.add_argument("--lr-schedule", default=None,
                    help="local-LR decay: linear | cosine | warmup_cosine")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()

    cfg = LM_100M
    bundle = get_bundle(cfg)
    n_params = cfg.param_count()
    print(f"model={cfg.name}: {n_params/1e6:.0f}M params, "
          f"{args.n_agents} agents, T_o={args.t_o}, p={args.p}")

    # heterogeneous per-agent streams (different bigram structure per agent)
    streams = [
        synthetic_lm_tokens(500_000, cfg.vocab_size, seed=31 * a + 1)
        for a in range(args.n_agents)
    ]
    rng = np.random.default_rng(0)

    def sample_round(_k):
        def one_set():
            out = []
            for a in range(args.n_agents):
                s = streams[a]
                starts = rng.integers(0, len(s) - args.seq - 1, size=args.batch)
                out.append(np.stack([s[i : i + args.seq] for i in starts]))
            return np.stack(out)

        sets = np.stack([one_set() for _ in range(args.t_o + 1)])
        local = {"tokens": jnp.asarray(sets[: args.t_o])}
        comm = {"tokens": jnp.asarray(sets[-1])}
        return local, comm

    pcfg = PiscoConfig(
        n_agents=args.n_agents, t_o=args.t_o, eta_l=args.eta_l, eta_c=1.0, p=args.p
    )
    mixing = sparse_mixing(make_sparse_topology("ring", args.n_agents))
    # Registry API: one bound algorithm (round fns + Bernoulli(p) schedule +
    # comm profile), one jitted scan over each block of rounds.  The same
    # UpdateRule API that drives the logreg experiments plugs in here — e.g.
    # `--local-opt momentum --server-opt fedadam` is PISCO-M with FedAdam
    # server rounds on a 126M-param LM.
    from repro.optim import resolve_update_rules

    opt_kw = resolve_update_rules(
        args.local_opt, args.server_opt, args.lr_schedule,
        eta_l=args.eta_l, rounds=args.rounds, t_o=args.t_o,
    )
    bound = get_algorithm("pisco").bind(bundle.loss, pcfg, mixing, **opt_kw)
    block_fn = make_block_fn(bound)
    acct = CommAccountant()

    params = bundle.init(jax.random.PRNGKey(0))
    x0 = replicate_params(params, args.n_agents)
    local0, comm0 = sample_round(-1)
    state = bound.init(bundle.loss, x0, comm0)

    losses = []
    t0 = time.perf_counter()
    k = 0
    while k < args.rounds:
        # blocks end at log points and checkpoint multiples
        stop = min(k + args.log_every, args.rounds)
        if args.ckpt_dir:
            stop = min(stop, ((k // 100) + 1) * 100)
        flags = predraw_schedule(bound.schedule, k, stop)
        local, comm = sample_block(sample_round, k, stop)
        state, metrics = block_fn(state, jnp.asarray(flags), local, comm)
        for f in flags:
            acct.record(bool(f))
        losses.extend(np.asarray(metrics.loss, dtype=np.float64).tolist())
        dt = time.perf_counter() - t0
        print(
            f"round {stop - 1:4d} [{'J' if flags[-1] else 'W'}] loss={losses[-1]:.4f} "
            f"consensus={float(metrics.consensus_err[-1]):.2e} ({dt/stop:.1f}s/round)"
        )
        if args.ckpt_dir and stop % 100 == 0:
            save_checkpoint(
                args.ckpt_dir, stop, state,
                metadata={"model": config_to_dict(cfg)},
            )
        k = stop

    if args.ckpt_dir:
        # final-state checkpoint regardless of round count; the manifest
        # carries the model config, so the serving launcher rebuilds the
        # bundle from the checkpoint alone
        path = save_checkpoint(
            args.ckpt_dir, args.rounds, state,
            metadata={"model": config_to_dict(cfg)},
        )
        print(f"saved final checkpoint: {path}")
        print(
            "serve it:  PYTHONPATH=src python -m repro.launch.serve "
            f"--ckpt {path} --delta topk:f=0.05"
        )
    print(
        f"\nfinal: loss {losses[0]:.4f} -> {losses[-1]:.4f} over {args.rounds} rounds "
        f"({acct.agent_to_agent} gossip / {acct.agent_to_server} server)"
    )
    assert losses[-1] < losses[0], "training must reduce loss"


if __name__ == "__main__":
    main()
