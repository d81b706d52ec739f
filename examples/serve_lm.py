"""Serving example: batched prefill + incremental decode through the KV/state
cache, on the MoE + sliding-window arch (mixtral) and the SSM arch (xlstm).

    PYTHONPATH=src python examples/serve_lm.py
"""

import time

import jax
import jax.numpy as jnp

from repro.compile_cache import configure_compile_cache
from repro.configs.registry import get_arch
from repro.launch.serve import generate
from repro.models import model

configure_compile_cache()

for arch in ("mixtral_8x7b", "xlstm_1p3b"):
    cfg = get_arch(arch).reduced()
    key = jax.random.PRNGKey(0)
    params, _ = model.init_params(cfg, key)
    b, prompt_len, gen_len = 4, 24, 12
    prompt = jax.random.randint(key, (b, prompt_len), 0, cfg.vocab_size, jnp.int32)

    t0 = time.time()
    out = generate(cfg, params, prompt, gen_len)
    dt = time.time() - t0
    print(
        f"{arch:16s} batch={b} prompt={prompt_len} generated={out.shape} "
        f"({b * gen_len / dt:.1f} tok/s on 1 CPU, reduced config)"
    )
    assert out.shape[1] == gen_len
print("serving OK: prefill->decode cache paths exact (see tests/test_decode_consistency.py)")
