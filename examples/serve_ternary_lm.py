"""Serve an LM with CUTIE-style ternary weights (the paper's technique
carried to the LM serving path).

Trains a small llama-family model briefly on the synthetic copy task,
quantizes the GEMM weights to packed 2-bit ternary, and serves batched
requests from both variants, reporting the weight-byte compression and
agreement.

Run:  PYTHONPATH=src python examples/serve_ternary_lm.py
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import TokenTaskConfig, token_batch
from repro.models import build_model
from repro.serving import ServeConfig, generate, quantize_for_serving
from repro.training import AdamWConfig, Trainer, TrainerConfig


def main():
    # llama3.2-family reduced config, widened to make quantization bite.
    cfg = dataclasses.replace(get_config("llama3.2-1b", smoke=True),
                              d_model=256, d_ff=512, num_heads=8,
                              num_kv_heads=4, head_dim=32)
    model = build_model(cfg)

    tk = TokenTaskConfig(vocab_size=cfg.vocab_size, seq_len=32,
                         batch_size=16, task="repeat")
    tr = Trainer(model, TrainerConfig(
        total_steps=60, ckpt_every=1000, log_every=20,
        ckpt_dir="checkpoints/serve_example",
        opt=AdamWConfig(lr=5e-3, warmup_steps=5, total_steps=60)),
        lambda s: token_batch(tk, s))
    print("training the base model on the copy task...")
    res = tr.run(jax.random.PRNGKey(0))
    params = res["state"]["params"]

    qparams, stats = quantize_for_serving(params)
    print(f"\nternary serving quantization: {stats['quantized']} tensors "
          f"packed, {stats['kept']} kept fp")
    print(f"  weight bytes {stats['bytes_before'] / 1e6:.1f} MB -> "
          f"{stats['bytes_after'] / 1e6:.1f} MB "
          f"({stats['bytes_before'] / stats['bytes_after']:.2f}x)")

    prompts = token_batch(tk, 999)["tokens"][:4, :8]
    sc = ServeConfig(max_new_tokens=12)
    toks_f, st_f = generate(model, params, prompts, sc)
    toks_q, st_q = generate(model, qparams, prompts, sc)
    agree = float((toks_f == toks_q).mean())
    print(f"\nfull-precision serve: {st_f.tokens_per_s:.1f} tok/s (host)")
    print(f"ternary serve:        {st_q.tokens_per_s:.1f} tok/s (host)")
    print(f"greedy token agreement: {agree:.2f}")
    print("full:    ", toks_f[0].tolist())
    print("ternary: ", toks_q[0].tolist())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
