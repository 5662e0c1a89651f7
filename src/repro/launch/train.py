"""Training launcher: mesh + rules + sharded state + trainer loop.

On real hardware this is the per-host entrypoint (jax.distributed handles
multi-host init); on this container it runs the same code path over however
many devices the process sees — which is exactly what the integration tests
exercise with forced host-device counts.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
      --steps 100 --batch 16 --seq 128 --ckpt-dir /tmp/run1

A preempted/killed run restarted with the same flags resumes from the last
checkpoint (elastic: the mesh may differ between runs).
"""

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import set_mesh

from repro.configs import get_config, get_smoke_config
from repro.data import RecordStore, TrainPipeline, synthetic_corpus
from repro.distributed.partitioning import axis_rules, rules_for_mesh
from repro.launch import specs as S
from repro.launch.mesh import host_device_mesh
from repro.models import build_model
from repro.train import AdamWConfig, make_train_step
from repro.train.step import init_train_state
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--samples", type=int, default=512)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if not cfg.embed_inputs or cfg.is_encdec:
        raise SystemExit("this CLI drives token-input decoder archs; see "
                         "examples/ for VLM/enc-dec batches")
    model = build_model(cfg)
    mesh = host_device_mesh(model_axis=args.model_axis)
    rules = rules_for_mesh(mesh)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    print(f"mesh {mesh_shape}, arch {cfg.name}")

    store = RecordStore(seq_len=args.seq)
    tok, lab = synthetic_corpus(args.samples, args.seq, cfg.vocab, seed=1)
    store.ingest(tok, lab)
    pipe = TrainPipeline(store, batch_size=args.batch, seed=0)

    with axis_rules(rules, mesh_shape), set_mesh(mesh):
        state = init_train_state(model, jax.random.PRNGKey(0))
        state_sh = S.train_state_shardings(
            mesh, jax.eval_shape(lambda: state)
        )
        state = jax.device_put(state, state_sh)
        opt = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                          decay_steps=args.steps)
        step_fn = jax.jit(
            make_train_step(model, opt, grad_accum=cfg.grad_accum),
            in_shardings=(state_sh, None), out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )

        def batches():
            for b in pipe.batches():
                yield {k: jnp.asarray(v) for k, v in b.items()}

        trainer = Trainer(
            step_fn, state, batches(),
            TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every, log_every=10),
            state_shardings=state_sh,
        )
        if trainer.try_restore():
            print(f"resumed from step {trainer.step}")
            trainer.batches = iter(
                {k: jnp.asarray(v) for k, v in b.items()}
                for b in pipe.batches(start_step=trainer.step)
            )
        history = trainer.run()
    for row in history:
        print(" ".join(f"{k}={v:.4g}" for k, v in row.items()))
    print(f"done at step {trainer.step}; stragglers: {trainer.straggler_steps}")


if __name__ == "__main__":
    main()
