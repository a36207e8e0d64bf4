"""Long-context serving with a CKM-compressed KV cache (beyond-paper demo).

    PYTHONPATH=src python examples/serve_kv_ckm.py

Prefills a small model on a long prompt, compresses each global-attention
layer's KV cache into weighted centroids (the paper's mixture-of-Diracs, on
keys), and decodes with [centroids + exact recent ring].  Reports the
attention-output fidelity vs the uncompressed cache and the memory ratio.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_smoke_config
from repro.models import layers as L
from repro.models import transformer as tfm
from repro.serve.kv_clustering import (
    attention_decode_compressed,
    build_compressed_cache,
)
from repro.utils.compile_cache import enable_compile_cache

S_PROMPT = 1024
N_CENTROIDS = 64
RING = 64


def main():
    enable_compile_cache()
    cfg = get_smoke_config("llama3.2-1b")
    params = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    dims = tfm.attn_dims(cfg, "attn")

    # A long prompt through layer 0's attention to get a real KV cloud.
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, S_PROMPT), 0, cfg.vocab_size)
    x = L.embed(params["embed"], tokens, jnp.float32) * jnp.sqrt(cfg.d_model)
    pos = jnp.broadcast_to(jnp.arange(S_PROMPT), (1, S_PROMPT))
    layer0 = jax.tree.map(lambda l: l, params["groups"])  # stacked (G, ...)
    p0 = jax.tree.map(lambda l: l[0], params["groups"]["0"])
    h = L.rmsnorm(p0["norm1"], x)
    _, (k, v) = L.attention_apply(p0["mixer"], dims, h, pos, return_kv=True)

    # Compress with both clusterers from the paper's toolbox.
    q_tok = h[:, -1:, :]
    out_full, _, _ = L.attention_decode(
        p0["mixer"], dims,
        q_tok,
        jnp.pad(k, ((0, 0), (0, 1), (0, 0), (0, 0))),
        jnp.pad(v, ((0, 0), (0, 1), (0, 0), (0, 0))),
        jnp.asarray(S_PROMPT),
    )
    for method in ("lloyd", "ckm"):
        cache = build_compressed_cache(
            jax.random.PRNGKey(2), k, v, N_CENTROIDS, RING, method=method
        )
        out_c, _ = attention_decode_compressed(
            p0["mixer"], dims, q_tok, cache, jnp.asarray(S_PROMPT)
        )
        rel = float(
            jnp.linalg.norm(out_c - out_full) / jnp.linalg.norm(out_full)
        )
        ratio = (S_PROMPT) / (N_CENTROIDS + RING)
        print(
            f"random-init KV  {method:6s}: rel err {rel:.4f} "
            f"({ratio:.1f}x smaller cache; random-init keys have no cluster "
            f"structure — worst case)"
        )

    # Real pretrained KV clouds cluster heavily; emulate that regime.
    kc_, ka, kn = jax.random.split(jax.random.PRNGKey(3), 3)
    centers = jax.random.normal(kc_, (N_CENTROIDS, cfg.n_kv_heads, cfg.head_dim_)) * 4
    assign = jax.random.randint(ka, (S_PROMPT,), 0, N_CENTROIDS)
    kcl = centers[assign][None] + 0.1 * jax.random.normal(kn, k.shape)
    vcl = centers[assign][None] * 0.5
    out_full_c, _, _ = L.attention_decode(
        p0["mixer"], dims, q_tok,
        jnp.pad(kcl, ((0, 0), (0, 1), (0, 0), (0, 0))),
        jnp.pad(vcl, ((0, 0), (0, 1), (0, 0), (0, 0))),
        jnp.asarray(S_PROMPT),
    )
    for method in ("lloyd", "ckm"):
        cache = build_compressed_cache(
            jax.random.PRNGKey(4), kcl, vcl, N_CENTROIDS, RING, method=method
        )
        out_c, _ = attention_decode_compressed(
            p0["mixer"], dims, q_tok, cache, jnp.asarray(S_PROMPT)
        )
        rel = float(jnp.linalg.norm(out_c - out_full_c) / jnp.linalg.norm(out_full_c))
        print(f"clustered KV    {method:6s}: rel err {rel:.4f} (pretrained-cache regime)")
    print(
        "\nnote: for LOCAL offline compression Lloyd is the right clusterer; "
        "CKM earns its keep when the cache is sharded across hosts — each "
        "host sketches its shard (O(m) traffic) and CLOMPR decodes centrally "
        "(see core.distributed_sketch)."
    )


if __name__ == "__main__":
    main()
