"""End-to-end training driver.

Two modes:
  * ``--stream`` (default): the paper's pipelined streaming schedule — data
    blocks arrive on the Fig.-2 timeline while the mesh trains on the
    delivered prefix; block size comes from the Corollary-1 planner unless
    ``--n-c`` overrides it.
  * ``--no-stream``: conventional training (all data available up front).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import INPUT_SHAPES, get_config, reduced
from repro.core import (BoundConstants, BoundPlanner, Scenario, Simulator,
                        StreamingTask)
from repro.data.synthetic import SyntheticTokens
from repro.models import init_params, make_train_step
from repro.optim import linear_warmup_cosine
from repro.optim.optimizers import make_optimizer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced CPU-scale config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--stream", dest="stream", action="store_true", default=True)
    ap.add_argument("--no-stream", dest="stream", action="store_false")
    ap.add_argument("--n-c", type=int, default=0, help="block size override")
    ap.add_argument("--n-o", type=float, default=8.0, help="per-block overhead")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"devices={jax.device_count()}")

    opt = make_optimizer(args.optimizer,
                         linear_warmup_cosine(args.lr, 10, args.steps))
    params = init_params(cfg, args.seed)
    opt_state = opt.init(params)
    train_step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))

    n_seqs = max(args.steps * args.batch // 4, args.batch * 4)
    data = SyntheticTokens(cfg.vocab_size, args.seq + 1, n_seqs, args.seed).batch(0)

    def make_batch(tok):
        return {"tokens": jnp.asarray(tok[:, : args.seq])}

    if args.stream:
        # the unified API: Scenario -> Planner -> Simulator
        scenario = Scenario(N=n_seqs, T=float(args.steps), n_o=args.n_o,
                            tau_p=1.0)
        consts = BoundConstants(L=1.0, c=0.05, M=1.0, M_G=1.0, D=2.0,
                                alpha=min(args.lr, 1.0))
        # --n-c pins the grid to the override; otherwise search 1..N
        planner = BoundPlanner(grid=[args.n_c] if args.n_c else None)
        plan = planner.plan(scenario, consts)
        if not args.n_c:
            print(f"planner: n_c-tilde = {plan.n_c} "
                  f"(bound {plan.bound_value:.4f})")
        task = StreamingTask(
            train_step=train_step, params=params, opt_state=opt_state,
            dataset=np.asarray(data), batch_size=args.batch,
            make_batch=make_batch, seed=args.seed)
        t0 = time.time()
        report = Simulator().run(scenario, plan, task)
        dt = time.time() - t0
        state = report.state
        losses = [h["loss"] for h in state.history]
        trace = (f"loss {losses[0]:.4f} -> {losses[-1]:.4f}" if losses
                 else "no logged updates (deadline too short for log_every)")
        print(f"streamed {report.delivered}/{n_seqs} seqs, "
              f"{state.step + 1} updates in {dt:.1f}s; {trace}")
        params = state.params
    else:
        step_j = jnp.zeros((), jnp.int32)
        rng = np.random.default_rng(args.seed)
        t0 = time.time()
        first = last = None
        for j in range(args.steps):
            idx = rng.integers(0, n_seqs, size=args.batch)
            batch = make_batch(data[idx])
            params, opt_state, m = train_step(params, opt_state, step_j, batch)
            step_j = step_j + 1
            loss = float(m["loss"])
            first = loss if first is None else first
            last = loss
        print(f"{args.steps} steps in {time.time()-t0:.1f}s; "
              f"loss {first:.4f} -> {last:.4f}")

    if args.ckpt:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(args.ckpt, params, step=args.steps)
        print(f"checkpoint written to {args.ckpt}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
