"""End-to-end PISCO training driver for the LM architectures.

Runs on whatever devices exist (the CPU container trains the reduced configs;
on a real pod the same code paths drive the production mesh — the step
functions are the ones the dry-run compiles).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
        --rounds 50 --t-o 4 --p 0.1 --batch 8 --seq 128

The host loop is the paper's line 8: a Bernoulli(p) draw per round picks the
pre-compiled gossip or global round function.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_checkpoint, restore_checkpoint, save_checkpoint
from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.core.algorithms import get_algorithm, registered_algorithms
from repro.core.compression import make_byte_model
from repro.core.driver import (
    dynamic_round_fns,
    make_block_fn,
    predraw_schedule,
    record_flags,
    sample_block,
)
from repro.core.adversary import (
    make_adversarial_mixing,
    parse_adversary_spec,
    unwrap_network,
)
from repro.core.experiment import Experiment, ExperimentSpec
from repro.core.mixing import make_sparse_network_mixing
from repro.core.pisco import PiscoConfig, replicate_params
from repro.core.trainer import History
from repro.core.topology import make_sparse_topology
from repro.optim.update_rules import RULE_NAMES, resolve_update_rules
from repro.data.synthetic import synthetic_lm_tokens
from repro.models import get_bundle
from repro.models.rope import mrope_text_positions
from repro.sim import PROFILE_NAMES, make_time_model, tune
from repro.utils.compile_cache import enable_compile_cache


def make_lm_sampler(cfg, n_agents: int, batch: int, seq: int, t_o: int, seed: int = 0):
    """Per-round sampler producing (local_batches, comm_batch) of LM batches.

    Heterogeneity: each agent's token stream uses a different Zipf shuffle —
    the LM analogue of the paper's sorted-label partition."""
    streams = [
        synthetic_lm_tokens(200_000, cfg.vocab_size, seed=seed + 17 * i)
        for i in range(n_agents)
    ]
    rng = np.random.default_rng(seed + 999)

    def batch_for(agent: int, b: int):
        s = streams[agent]
        starts = rng.integers(0, len(s) - seq - 1, size=b)
        toks = np.stack([s[st : st + seq] for st in starts])
        return toks

    def per_round(_k: int):
        def stacked(n_sets):
            toks = np.stack(
                [
                    np.stack([batch_for(a, batch) for a in range(n_agents)])
                    for _ in range(n_sets)
                ]
            )  # (n_sets, A, b, seq)
            return toks

        all_toks = stacked(t_o + 1)
        extra = {}
        local = {"tokens": jnp.asarray(all_toks[:t_o]), **extra}
        comm = {"tokens": jnp.asarray(all_toks[-1]), **extra}
        if cfg.modality == "vlm":
            n_patch = max(1, seq // 8)
            d = cfg.d_model
            local["prefix_embeds"] = jnp.asarray(
                rng.normal(size=(t_o, n_agents, batch, n_patch, d)).astype(np.float32)
            ).astype(jnp.dtype(cfg.dtype))
            comm["prefix_embeds"] = local["prefix_embeds"][0]
            pos = np.asarray(mrope_text_positions(batch, seq + n_patch))
            local["positions"] = jnp.asarray(
                np.broadcast_to(pos[None, None], (t_o, n_agents) + pos.shape).copy()
            )
            comm["positions"] = local["positions"][0]
        if cfg.is_enc_dec:
            t_frames = max(1, seq // 4)
            local["frames"] = jnp.asarray(
                rng.normal(size=(t_o, n_agents, batch, t_frames, cfg.d_model)).astype(
                    np.float32
                )
            ).astype(jnp.dtype(cfg.dtype))
            comm["frames"] = local["frames"][0]
        return local, comm

    return per_round


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS), required=True)
    ap.add_argument("--reduced", action="store_true", help="use the smoke-size config")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--n-agents", type=int, default=4)
    ap.add_argument("--t-o", type=int, default=2)
    ap.add_argument("--p", type=float, default=0.1)
    ap.add_argument("--eta-l", type=float, default=0.05)
    ap.add_argument("--eta-c", type=float, default=1.0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--network", default=None,
                    help="dynamic-topology process: static | bernoulli[:q] | "
                         "matching | roundrobin[:n] | cohort[:frac] "
                         "(default: frozen base W)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of agents sampled into each server round")
    ap.add_argument("--cohort", type=float, default=None,
                    help="neighbor-sampled cohorts: fraction of agents "
                         "seeding each gossip round (sugar for "
                         "--network cohort:FRAC)")
    ap.add_argument("--adversary", default=None,
                    help="Byzantine fault injection (DESIGN.md §14): "
                         "signflip[:f=..,scale=..] | random:f=..,scale=.. | "
                         "collusion:f=..,target=drift — the selected agents "
                         "corrupt their outgoing gossip payloads and server "
                         "uploads (default: none)")
    ap.add_argument("--robust-agg", default="mean",
                    help="server-averaging rule at global rounds: mean "
                         "(default, plain average) | trimmed[:f=..] | "
                         "median | krum[:f=..]")
    ap.add_argument("--systems", default=None,
                    help="simulated systems-cost profile (DESIGN.md §11): "
                         f"{'|'.join(PROFILE_NAMES)} with k=v overrides, e.g. "
                         "'wan-gossip' or 'uniform:latency=0'; prints the "
                         "simulated wall-clock split after training")
    ap.add_argument("--tune", action="store_true",
                    help="instead of training, run the p x tau communication "
                         "autotuner under --systems (default profile: "
                         "uniform) and print the simulated time-to-target "
                         "frontier")
    ap.add_argument("--tune-p", type=float, nargs="+",
                    default=[0.0, 0.05, 0.1, 0.3, 1.0],
                    help="server-probability grid for --tune")
    ap.add_argument("--tune-tau", type=int, nargs="+", default=None,
                    help="local-update (T_o) grid for --tune "
                         "(default: just --t-o)")
    ap.add_argument("--tune-rounds", type=int, default=None,
                    help="round budget per tuner configuration "
                         "(default: --rounds)")
    ap.add_argument("--tune-strategy", default="halving",
                    choices=["grid", "halving"],
                    help="sweep every config fully, or successive-halving")
    ap.add_argument("--algo", default="pisco", choices=list(registered_algorithms()))
    ap.add_argument("--local-opt", default=None,
                    help="pluggable local update rule (DESIGN.md §10): "
                         f"{'|'.join(RULE_NAMES)} with k=v args, e.g. "
                         "'momentum:beta=0.9' or 'clip:1.0|adam' "
                         "(default: the bit-exact hardcoded-SGD path)")
    ap.add_argument("--server-opt", default=None,
                    help="FedOpt server rule at global-averaging rounds: "
                         "fedavgm | fedadam | sgd:lr=... | momentum | adam")
    ap.add_argument("--lr-schedule", default=None,
                    help="per-round local-LR decay: linear[:final=..] | "
                         "cosine[:final=..] | warmup_cosine[:warmup=..]")
    ap.add_argument("--opt-policy", default=None,
                    choices=["mix", "keep", "reset"],
                    help="what happens to agent-stacked optimizer buffers at "
                         "communication rounds (default: registry entry's)")
    ap.add_argument("--driver", default="scan",
                    choices=["scan", "loop", "events"],
                    help="scan: chunked on-device lax.scan; loop: legacy host "
                         "loop; events: async event-queue over --systems "
                         "(repro.events, DESIGN.md §13)")
    ap.add_argument("--async", dest="async_spec", default=None,
                    help="async aggregation rule for --driver events: "
                         "'<rule>[:k=v,...]' over constant|poly|buffer with "
                         "keys alpha/bound/buffer, e.g. "
                         "'poly:alpha=0.5,bound=2,buffer=4'")
    ap.add_argument("--staleness-bound", type=int, default=None,
                    help="gossip staleness bound B (events driver): agents "
                         "more than B rounds behind the front are dropped "
                         "from their neighbors' mixes until the next server "
                         "reset")
    ap.add_argument("--buffer-size", type=int, default=None,
                    help="server buffer size m (events driver): a global "
                         "round fires at the m-th participant push instead "
                         "of waiting for the straggler tail")
    ap.add_argument("--block-size", type=int, default=16,
                    help="rounds per scan block (scan driver)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace of the run "
                         "(per-round spans with byte/sim-second attribution; "
                         "open the JSON at ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None,
                    help="append the run's metrics-registry snapshot "
                         "(rounds/bytes/sim-seconds counters + histograms) "
                         "as one line of this JSONL file")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of training into DIR "
                         "(open in TensorBoard's profile plugin)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    bundle = get_bundle(cfg)
    pcfg = PiscoConfig(
        n_agents=args.n_agents, t_o=args.t_o, eta_l=args.eta_l,
        eta_c=args.eta_c, p=args.p, seed=args.seed,
    )
    if args.cohort is not None and args.network is not None:
        ap.error("--cohort is sugar for --network cohort:FRAC; pass one, not both")
    network = (
        f"cohort:{args.cohort:g}" if args.cohort is not None else args.network
    )
    topo = make_sparse_topology(args.topology, args.n_agents)
    mixing = make_sparse_network_mixing(
        topo, network, args.participation, seed=args.seed
    )
    # fault injection + robust server rule compose as a mixing wrapper, the
    # same way ExperimentSpec.make_mixing layers them (before compression)
    mixing = make_adversarial_mixing(
        mixing, args.adversary, args.robust_agg,
        n_agents=args.n_agents, seed=args.seed,
    )
    lam = "n/a" if topo.lambda_w is None else f"{topo.lambda_w:.4f}"
    print(f"arch={cfg.name} params~{cfg.param_count():,} agents={args.n_agents} "
          f"topology={args.topology} "
          f"network={network or 'frozen'} "
          f"participation={args.participation:g} lambda_w={lam} "
          f"p={args.p}")
    if args.adversary is not None or args.robust_agg != "mean":
        adv = (
            parse_adversary_spec(args.adversary, args.n_agents, args.seed)
            if args.adversary is not None else None
        )
        print(f"adversary={args.adversary or 'none'}"
              + (f" ({adv.n_byz}/{args.n_agents} Byzantine)" if adv else "")
              + f" robust_agg={args.robust_agg}")

    sampler = make_lm_sampler(cfg, args.n_agents, args.batch, args.seq, args.t_o, args.seed)
    key = jax.random.PRNGKey(args.seed)
    params = bundle.init(key)
    x0 = replicate_params(params, args.n_agents)

    async_spec = args.async_spec
    if args.staleness_bound is not None or args.buffer_size is not None:
        from repro.events.staleness import AsyncConfig, parse_async_spec
        import dataclasses as _dc

        acfg = parse_async_spec(async_spec) if async_spec else AsyncConfig()
        if args.staleness_bound is not None:
            acfg = _dc.replace(acfg, bound=args.staleness_bound)
        if args.buffer_size is not None:
            acfg = _dc.replace(acfg, buffer=args.buffer_size)
        async_spec = acfg.spec()
    if async_spec is not None and args.driver != "events":
        ap.error("--async/--staleness-bound/--buffer-size need --driver events")
    if args.driver == "events" and not args.systems:
        ap.error("--driver events needs --systems (the event clock is drawn "
                 "from the fleet profile)")

    # Declarative twin of this CLI invocation — what the sim cost model and
    # the autotuner price (network/participation/systems draws are pure
    # functions of this spec).
    spec = ExperimentSpec.create(
        algo=args.algo, n_agents=args.n_agents, t_o=args.t_o,
        eta_l=args.eta_l, eta_c=args.eta_c, p=args.p, seed=args.seed,
        topology=args.topology, network=args.network, cohort=args.cohort,
        participation=args.participation,
        systems=args.systems or ("uniform" if args.tune else None),
        async_=async_spec,
        adversary=args.adversary, robust_agg=args.robust_agg,
        optimizer=args.local_opt, server_optimizer=args.server_opt,
        lr_schedule=args.lr_schedule, opt_policy=args.opt_policy,
        rounds=args.rounds, driver=args.driver, block_size=args.block_size,
    )
    if args.tune:
        result = tune(
            spec,
            dict(
                loss_fn=bundle.loss, params0=params,
                sampler_factory=lambda s: make_lm_sampler(
                    cfg, args.n_agents, args.batch, args.seq,
                    s.config.t_o, args.seed,
                ),
            ),
            p_grid=args.tune_p,
            tau_grid=tuple(args.tune_tau) if args.tune_tau else (None,),
            rounds=args.tune_rounds,
            strategy=args.tune_strategy,
        )
        print(f"tuner ({result.strategy}) under {result.systems!r}: "
              f"target smoothed loss {result.target_loss:.4f}")
        print(f"{'p':>6} {'T_o':>4} {'rounds':>6} {'sim s->target':>13} "
              f"{'total sim s':>11} {'final loss':>10}")
        for pt in result.points:
            tts = (
                f"{pt.time_to_target_s:13.2f}"
                if pt.time_to_target_s is not None else f"{'---':>13}"
            )
            print(f"{pt.p:6.2f} {pt.t_o:4d} {pt.rounds_run:6d} {tts} "
                  f"{pt.total_sim_time_s:11.2f} {pt.final_loss:10.4f}")
        print(f"fastest-to-target: p={result.best.p:g} T_o={result.best.t_o}")
        return 0

    recorder = None
    if args.trace_out:
        from repro.obs import TraceRecorder

        recorder = TraceRecorder(meta={
            "kind": "train", "arch": cfg.name, "algo": args.algo,
            "driver": args.driver, "n_agents": args.n_agents,
            "rounds": args.rounds, "systems": args.systems,
        })

    def write_telemetry(hist) -> None:
        if args.trace_out:
            from repro.obs import write_trace

            write_trace(args.trace_out, recorder)
            print(f"trace written to {args.trace_out} (open at ui.perfetto.dev)")
        if args.metrics_out:
            hist.telemetry(meta=dict(recorder.meta) if recorder else {
                "kind": "train", "arch": cfg.name, "algo": args.algo,
                "driver": args.driver,
            }).write_jsonl(args.metrics_out)
            print(f"metrics appended to {args.metrics_out}")

    if args.driver == "events":
        if args.ckpt_dir:
            ap.error("checkpointing is not supported with --driver events")
        from repro.obs import profile_capture

        with profile_capture(args.profile):
            hist = Experiment(
                spec, loss_fn=bundle.loss, params0=params, sampler=sampler,
                recorder=recorder,
            ).run()
        srv = np.asarray(hist.is_global, dtype=bool)
        secs = np.asarray(hist.sim_time_s, dtype=np.float64)
        stale = np.asarray(hist.staleness, dtype=np.int64)
        for k in range(0, args.rounds, max(1, args.log_every)):
            print(f"round {k:4d} [{'J' if hist.is_global[k] else 'W'}] "
                  f"loss={hist.loss[k]:.4f} sim_t={secs[: k + 1].sum():.2f}s "
                  f"max_staleness={int(stale[k].max())}")
        print(
            f"done (events, async={spec.async_ or 'constant'}): "
            f"{args.rounds} rounds, simulated {secs.sum():.2f}s under "
            f"{args.systems!r} (gossip {secs[~srv].sum():.2f}s / "
            f"{int((~srv).sum())} rounds, server {secs[srv].sum():.2f}s / "
            f"{int(srv.sum())} rounds, peak staleness {int(stale.max())})"
        )
        write_telemetry(hist)
        return 0

    start_round = 0
    ckpt_tree = None
    if args.ckpt_dir:
        latest = latest_checkpoint(args.ckpt_dir)
        if latest:
            start_round, ckpt_tree = restore_checkpoint(latest)
            print(f"restored {latest} at round {start_round}")

    opt_kw = resolve_update_rules(
        args.local_opt, args.server_opt, args.lr_schedule, args.opt_policy,
        eta_l=args.eta_l, rounds=args.rounds, t_o=args.t_o,
    )
    if opt_kw:
        lo, so = opt_kw.get("local_opt"), opt_kw.get("server_opt")
        print(f"update rules: local={lo.name if lo else 'sgd (default)'} "
              f"server={so.name if so else 'none'} "
              f"policy={opt_kw.get('opt_policy', 'registry default')}")
    bound = get_algorithm(args.algo).bind(bundle.loss, pcfg, mixing, **opt_kw)
    # The launcher funnels flag/byte/second recording through the same
    # History + record_flags seam the Experiment drivers use, so telemetry
    # (--trace-out / --metrics-out) threads uniformly.
    hist = History(
        byte_model=make_byte_model(
            mixing, x0, args.n_agents,
            mixes_per_round=bound.comm.mixes_per_round,
            server_payloads=bound.comm.server_payloads,
        )
    )
    if args.systems:
        hist.time_model = make_time_model(
            spec, hist.byte_model, network=unwrap_network(bound.network)
        )
    hist.recorder = recorder
    acct = hist.accountant

    local0, comm0 = sampler(-1)
    state = bound.init(bundle.loss, x0, comm0)
    if ckpt_tree is not None:
        # the checkpoint stores namedtuples as plain tuples; pour its leaves
        # back into the freshly-initialized state's structure (which also
        # validates that the bound algorithm/optimizer matches the snapshot)
        treedef = jax.tree.structure(state)
        leaves = jax.tree.leaves(ckpt_tree)
        if len(leaves) != treedef.num_leaves:
            raise ValueError(
                f"checkpoint has {len(leaves)} leaves but the bound "
                f"algorithm state needs {treedef.num_leaves} — was it saved "
                f"with different --algo/--local-opt/--server-opt settings?"
            )
        state = jax.tree.unflatten(
            treedef, [jnp.asarray(leaf) for leaf in leaves]
        )
    # The state owns its buffers; dropping the launcher's copies of the
    # initial point leaves one agent-stacked state on the device.
    del params, x0
    from repro.obs import profile_capture

    t0 = time.perf_counter()
    _prof = contextlib.ExitStack()
    _prof.enter_context(profile_capture(args.profile))
    net = bound.network
    if args.driver == "loop":
        if net is not None:
            gossip_fn, global_fn = dynamic_round_fns(bound)
        else:
            gossip_fn = jax.jit(bound.gossip_round)
            global_fn = (
                jax.jit(bound.global_round)
                if bound.global_round is not bound.gossip_round else gossip_fn
            )
        for k in range(start_round, args.rounds):
            local, comm = sampler(k)
            is_global = bool(bound.schedule(k))
            record_flags(hist, np.array([is_global]), start=k)
            fn = global_fn if is_global else gossip_fn
            if net is not None:
                w_gossip, w_server, _, _ = net.draw_round(k)
                state, metrics = fn(
                    state, local, comm,
                    jax.tree.map(jnp.asarray, w_gossip),
                    jax.tree.map(jnp.asarray, w_server),
                )
            else:
                state, metrics = fn(state, local, comm)
            if k % args.log_every == 0 or k == args.rounds - 1:
                print(
                    f"round {k:4d} [{'J' if is_global else 'W'}] "
                    f"loss={float(metrics.loss):.4f} "
                    f"|grad|^2={float(metrics.grad_sq_norm):.3e} "
                    f"consensus={float(metrics.consensus_err):.3e}"
                )
            if args.ckpt_dir and args.ckpt_every and (k + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, k + 1, state)
    else:
        # Scan driver: pre-draw the Bernoulli(p) flags for each block on the
        # host, run the block on-device, sync only at log/checkpoint cuts.
        block_fn = make_block_fn(bound)
        k = start_round
        while k < args.rounds:
            stop = min(k + args.block_size, args.rounds)
            nxt_log = k if k % args.log_every == 0 else (
                (k // args.log_every + 1) * args.log_every
            )
            if nxt_log < args.rounds:
                stop = min(stop, nxt_log + 1)
            if args.ckpt_dir and args.ckpt_every:
                stop = min(stop, (k // args.ckpt_every + 1) * args.ckpt_every)
            flags = predraw_schedule(bound.schedule, k, stop)
            local, comm = sample_block(sampler, k, stop)
            if net is not None:
                w_gossip, w_server, _, _ = net.draw_block(k, stop)
                state, metrics = block_fn(
                    state, jnp.asarray(flags), jax.tree.map(jnp.asarray, w_gossip),
                    jax.tree.map(jnp.asarray, w_server), local, comm,
                )
            else:
                state, metrics = block_fn(state, jnp.asarray(flags), local, comm)
            record_flags(hist, flags, start=k)
            k_end = stop - 1
            if k_end % args.log_every == 0 or k_end == args.rounds - 1:
                print(
                    f"round {k_end:4d} [{'J' if flags[-1] else 'W'}] "
                    f"loss={float(metrics.loss[-1]):.4f} "
                    f"|grad|^2={float(metrics.grad_sq_norm[-1]):.3e} "
                    f"consensus={float(metrics.consensus_err[-1]):.3e}"
                )
            if args.ckpt_dir and args.ckpt_every and stop % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, stop, state)
            k = stop
    _prof.close()
    dt = time.perf_counter() - t0
    hist.wall_time_s = dt
    print(
        f"done: {args.rounds} rounds in {dt:.1f}s "
        f"({acct.agent_to_agent} gossip, {acct.agent_to_server} server rounds)"
    )
    if args.systems:
        # recorded online by record_flags through the attached time model —
        # identical to the old post-hoc price_rounds pass
        secs = np.asarray(hist.sim_time_s, dtype=np.float64)
        srv = np.asarray(hist.is_global, dtype=bool)
        print(
            f"simulated time under {args.systems!r}: {secs.sum():.2f}s "
            f"(gossip {secs[~srv].sum():.2f}s / {int((~srv).sum())} rounds, "
            f"server {secs[srv].sum():.2f}s / {int(srv.sum())} rounds)"
        )
    write_telemetry(hist)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
