"""Federated training launcher.

Runs FOLB (or a baseline algorithm) rounds of the production round engine
on whatever devices exist — the production entry point on a real TPU pod,
and a runnable CPU driver at reduced scale (see examples/).

  PYTHONPATH=src python -m repro.launch.train --arch fed100m --rounds 20 \
      --clients 4 --seqs-per-client 2 --seq-len 256 --algo folb
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt_io
from repro.configs import get_config
from repro.data.synthetic import token_stream_lm
from repro.fed.distributed import RoundConfig, folb_round
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as model_lib
from repro.sharding import specs as specs_lib
from repro.sharding.context import use_sharding


def make_round_batches(cfg, n_clients: int, seqs: int, seq_len: int,
                       n_rounds: int, seed: int = 0):
    """Pre-generate per-round client batches from the non-IID LM streams."""
    devices = token_stream_lm(seed, n_clients * n_rounds, cfg.vocab, seq_len,
                              docs_per_device=seqs)
    batches = []
    for r in range(n_rounds):
        devs = devices[r * n_clients:(r + 1) * n_clients]
        batches.append({
            "tokens": jnp.asarray(np.stack([d["tokens"] for d in devs])),
            "labels": jnp.asarray(np.stack([d["labels"] for d in devs])),
        })
    return batches


def init_placed(cfg, mesh, seed: int = 0):
    """Seeded parameters placed on ``mesh`` under the production param
    specs -> (params, their NamedSharding pytree)."""
    params = model_lib.init_params(cfg, jax.random.PRNGKey(seed))
    ps = jax.eval_shape(lambda: params)
    p_shard = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s),
        specs_lib.param_specs(cfg, ps, mesh),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    return jax.device_put(params, p_shard), p_shard


def make_step(cfg, rc: RoundConfig, mesh, p_shard):
    """The jitted federated round ``(params, batch) -> (params, metrics)``
    exactly as the launcher runs it."""
    @jax.jit
    def step(p, b):
        with use_sharding(mesh):
            return folb_round(cfg, rc, p, b, param_shardings=p_shard)
    return step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="fed100m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant of the arch")
    ap.add_argument("--algo", default="folb",
                    choices=["fedavg", "fedprox", "folb", "folb_het"])
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--seqs-per-client", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--mu", type=float, default=0.01)
    ap.add_argument("--psi", type=float, default=0.0)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rc = RoundConfig(algo=args.algo, n_clients=args.clients,
                     local_steps=args.local_steps, lr=args.lr, mu=args.mu,
                     psi=args.psi, remat=True)
    mesh = make_host_mesh(args.model_parallel)
    print(f"[train] {cfg.name} | algo={args.algo} K={args.clients} "
          f"E={args.local_steps} | mesh {dict(mesh.shape)}")

    params, p_shard = init_placed(cfg, mesh, args.seed)
    step = make_step(cfg, rc, mesh, p_shard)
    batches = make_round_batches(cfg, args.clients, args.seqs_per_client,
                                 args.seq_len, args.rounds, args.seed)
    for r, batch in enumerate(batches):
        t0 = time.time()
        params, metrics = step(params, batch)
        loss = float(metrics["client_loss"])
        print(f"[round {r:3d}] client_loss={loss:.4f} "
              f"g1_norm={float(metrics['g1_norm']):.3f} "
              f"denom={float(metrics['weight_denom']):.3f} "
              f"({time.time()-t0:.1f}s)")
        if args.ckpt_dir and (r + 1) % 10 == 0:
            ckpt_io.save_checkpoint(f"{args.ckpt_dir}/step_{r+1}", params,
                                    step=r + 1, extra={"arch": cfg.name})
    if args.ckpt_dir:
        ckpt_io.save_checkpoint(f"{args.ckpt_dir}/step_{len(batches)}",
                                params, step=len(batches),
                                extra={"arch": cfg.name})
    print("[train] done")


if __name__ == "__main__":
    main()
