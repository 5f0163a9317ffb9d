"""Batched long-context serving with a sequence-sharded KV cache.

Demonstrates the survey-§4.1.4-adapted decode path: prefill a prompt, then
decode with the KV cache sharded (batch @ data, seq @ model) across an 8-device
host mesh, using the logsumexp-combine distributed attention. Greedy decoding
from the mamba2 (O(1)-state) and gemma2 (sliding-window) reduced configs shows
both long_500k-eligible cache disciplines.

    PYTHONPATH=src python examples/serve_longcontext.py
"""

import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses                                      # noqa: E402

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import ParallelPlan, get_smoke_config, sharding  # noqa: E402
from repro.launch.mesh import make_mesh                 # noqa: E402
from repro.models import build_model                    # noqa: E402


def serve(arch: str, max_ctx: int = 256, gen: int = 32):
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_smoke_config(arch)
    if cfg.sliding_window:
        cfg = dataclasses.replace(cfg, sliding_window=64, long_context=True)
    plan = ParallelPlan(remat="none", compute_dtype="float32",
                        seq_shard_decode=True)
    model = build_model(cfg, plan, mesh, ("data",))
    params = model.init(jax.random.PRNGKey(0))

    b = 4
    cache = model.init_cache(b, max_ctx)
    cspecs = sharding.cache_specs(cache, plan, mesh, ("data",))
    cache = jax.device_put(cache, jax.tree.map(
        lambda s: NamedSharding(mesh, s), cspecs,
        is_leaf=lambda x: isinstance(x, P)))
    kv_like = [k for k in ("k", "attn_k") if isinstance(cache, dict) and k in cache]
    for k in kv_like:
        print(f"{arch}: cache[{k}] {cache[k].shape} sharded "
              f"{cache[k].sharding.spec}")

    step = jax.jit(model.decode_step, donate_argnums=(1,))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, (b, 16)).astype(np.int32)

    out_tokens = []
    if "prefill" in model.extras:
        # production path: parallel prefill emits the KV cache in one pass,
        # then the cache is laid out (batch@data, seq@model) for decode
        logits_all, cache = model.extras["prefill"](
            params, {"tokens": jnp.asarray(prompt)}, max_seq=max_ctx)
        cache = jax.device_put(cache, jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            sharding.cache_specs(cache, plan, mesh, ("data",)),
            is_leaf=lambda x: isinstance(x, P)))
        logits = logits_all[:, -1]
        pos = prompt.shape[1]
    else:
        # SSM state has no parallel-prefill shortcut here: run the recurrence
        pos = 0
        for t in range(prompt.shape[1]):
            logits, cache = step(params, cache, jnp.asarray(prompt[:, t]),
                                 jnp.int32(pos))
            pos += 1
    for _ in range(gen):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out_tokens.append(np.asarray(tok))
        logits, cache = step(params, cache, tok, jnp.int32(pos))
        pos += 1
    gen_arr = np.stack(out_tokens, 1)
    assert np.isfinite(np.asarray(logits)).all()
    print(f"{arch}: generated {gen_arr.shape} tokens, "
          f"first row: {gen_arr[0][:10]}...")


def main():
    serve("mamba2-370m")        # O(1) recurrent state decode
    serve("gemma2-9b")          # sliding-window seq-sharded KV decode
    print("long-context serving OK")


if __name__ == "__main__":
    main()
