"""Train an LM with delta-based checkpointing + historical queries over
training state — the paper's storage model as the fault-tolerance layer.

Runs a few hundred steps of a small smollm-family model on CPU, injects
two node failures, recovers from the delta chain, then answers
historical queries about the run (point / diff / agg over loss and
per-tensor norms) and reconstructs an intermediate optimizer state
bit-exactly.

  PYTHONPATH=src python examples/train_lm_delta_ckpt.py \
      [--steps 200] [--preset 100m]
"""
import argparse
import tempfile
import time

import jax
import numpy as np

from repro.checkpoint import DeltaPolicy
from repro.config import ShardingConfig, TrainConfig, reduced
from repro.configs import get_config
from repro.runtime import FailureInjector, init_train_state
from repro.launch.train import train


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--preset", choices=["tiny", "100m"], default="tiny",
                    help="tiny: CPU-friendly demo; 100m: ~100M params "
                    "(slow on 1 CPU core — intended for a real device)")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    if args.preset == "100m":
        cfg = reduced(get_config("smollm-360m"), n_layers=12,
                      d_model=768, n_heads=12, n_kv_heads=4, head_dim=64,
                      d_ff=2048, vocab=32768, max_seq=1024)
        tcfg = TrainConfig(global_batch=8, seq_len=512, lr=3e-4,
                           total_steps=args.steps,
                           warmup_steps=max(args.steps // 10, 1))
    else:
        cfg = reduced(get_config("smollm-360m"), n_layers=2, d_model=128,
                      n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256,
                      vocab=2048)
        tcfg = TrainConfig(global_batch=8, seq_len=128, lr=3e-3,
                           total_steps=args.steps,
                           warmup_steps=max(args.steps // 10, 1),
                           param_dtype="float32")

    n_params = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: init_train_state(
            jax.random.PRNGKey(0), cfg, tcfg)).params))
    print(f"model: {cfg.name}-reduced, {n_params/1e6:.1f}M params, "
          f"{args.steps} steps")

    ckpt_dir = args.ckpt or tempfile.mkdtemp(prefix="delta_ckpt_")
    injector = FailureInjector(fail_at=(args.steps // 3,
                                        2 * args.steps // 3))
    t0 = time.time()
    state, history, store = train(
        cfg, tcfg, ShardingConfig(), ckpt_dir=ckpt_dir, ckpt_every=10,
        policy=DeltaPolicy(kind="opcount", op_budget=3 * n_params),
        injector=injector, log_every=10, log_tensor_norms=True)
    print(f"[train] {args.steps} steps in {time.time()-t0:.1f}s with 2 "
          f"injected failures (recovered from delta chain)")
    print(f"[train] loss {history.rows['loss'][0]:.3f} -> "
          f"{history.rows['loss'][-1]:.3f}")

    # ---- historical queries over training dynamics (paper Table 1) ----
    steps = history.steps
    mid = steps[len(steps) // 2]
    print(f"[hist] point:  loss at step {mid} = "
          f"{history.point('loss', mid):.3f}")
    print(f"[hist] diff:   |Δ global param norm| over "
          f"[{steps[0]},{steps[-1]}] = "
          f"{history.diff('norm/__global__', steps[0], steps[-1]):.3f}")
    print(f"[hist] agg:    mean grad-norm over run = "
          f"{history.agg('grad_norm', steps[0], steps[-1]):.3f}")

    # ---- two-phase plan on actual state: reconstruct a past step ----
    template = jax.eval_shape(lambda: init_train_state(
        jax.random.PRNGKey(tcfg.seed), cfg, tcfg))
    logged = store.manifest["steps"]
    target = logged[len(logged) // 2]
    t0 = time.time()
    past = store.restore(target, template, method="ops")
    print(f"[restore] state @ step {target} reconstructed from "
          f"{store.select_anchor(target)}-anchored delta chain in "
          f"{(time.time()-t0)*1e3:.0f} ms (bit-exact)")
    b = store.storage_bytes()
    full_one = sum(x.size * np.dtype("float32").itemsize //
                   (1 if str(x.dtype) == "float32" else 2)
                   for x in jax.tree.leaves(template))
    print(f"[storage] snapshots {b['snapshots']/1e6:.1f} MB, deltas "
          f"{b['deltas']/1e6:.1f} MB "
          f"({len(store.manifest['snapshots'])} materialized snapshots, "
          f"{len(store.manifest['deltas'])} deltas)")


if __name__ == "__main__":
    main()
