"""Batched serving example: prefill + incremental decode with KV caches /
recurrent states, across three architecture families (dense KV cache,
MoE, and an O(1)-state xLSTM — the long_500k-capable family).

  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.lm.configs import get_config
from repro.lm.models.model import Model
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

for arch in ("phi3-medium-14b", "granite-moe-1b-a400m", "xlstm-125m"):
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    key = jax.random.PRNGKey(0)
    params, _ = model.init(key)
    B, S, GEN = 4, 12, 6
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    caches = model.init_cache(B, S + GEN)
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)

    logits, caches = prefill(params, {"tokens": toks}, caches)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [np.asarray(tok)[:, 0]]
    t0 = time.time()
    for i in range(GEN - 1):
        logits, caches = decode(params, tok, caches,
                                jnp.asarray(S + i, jnp.int32))
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(np.asarray(tok)[:, 0])
    dt = time.time() - t0
    gen = np.stack(out, 1)
    state_kind = ("recurrent state" if cfg.family == "ssm" else "KV cache")
    print(f"{arch:22s} [{cfg.family:6s}] generated {gen.shape} via "
          f"{state_kind}; {B * (GEN - 1) / max(dt, 1e-9):7.1f} tok/s "
          f"sample={gen[0].tolist()}")
