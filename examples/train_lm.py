"""Train an assigned-architecture LM (reduced width by default) with the
full production loop: sharded mesh, AdamW (factored v / bf16 momentum),
deterministic pipeline, async checkpointing, automatic resume.

    PYTHONPATH=src python examples/train_lm.py --arch qwen3-moe-235b-a22b \
        --steps 50 --smoke
    # full-size configs need a real TPU mesh; --smoke runs the reduced config
"""
import argparse

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, get_smoke
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_host_mesh
from repro.optim.adamw import OptConfig
from repro.runtime.train_loop import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-friendly)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh(tp=2)
    print(f"training {cfg.name} on mesh {dict(mesh.shape)}")
    res = train(
        cfg, mesh, steps=args.steps,
        dcfg=DataConfig(seed=0, batch=args.batch, seq_len=args.seq),
        opt_cfg=OptConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps,
                          m_dtype="bfloat16", v_mode="factored"),
        ckpt_dir=args.ckpt_dir, ckpt_every=25,
    )
    if res.restored_from:
        print(f"(resumed from checkpointed step {res.restored_from})")
    k = max(len(res.losses) // 10, 1)
    for i in range(0, len(res.losses), k):
        print(f"step {i + (res.restored_from or 0):5d}  loss {res.losses[i]:.4f}")
    print(f"final loss {res.losses[-1]:.4f}  skipped(NaN-guard)={res.skipped_steps}")


if __name__ == "__main__":
    main()
