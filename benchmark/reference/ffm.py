"""Field-aware factorization machine (Juan, Zhuang, Chin, Lin 2016), one
table row [w, v_{.,0}, ..., v_{.,nf-1}] per feature (v_{.,c}: the
feature's k factors against field c), binary features, the feature in
column i of a row lying in field i:
    logit = sum_i w_i + sum_{i<j} <v_{i,j}, v_{j,i}>
"""

import math

ROW_BLOCK = 4096  # rows at a time: what a block holds beside the gathered rows stays small


def width(cfg: dict) -> int:
    return 1 + int(cfg["num_fields"]) * int(cfg["v_dim"])


def leaves(cfg: dict) -> dict:
    return {"w": slice(0, 1), "v": slice(1, width(cfg))}


def logits(rows, cfg: dict):
    """rows [B, F, 1 + nf*k] -> [B], F == nf. The sum over pairs as it
    is written: for each i, its factors against every later field j
    times feature j's factors against field i. Elementwise products and
    sums only, in the rows' own type. The loop over i is a scan and the
    rows go in blocks, both recomputed in the backward pass, so one body
    is compiled and nothing the size of the batch is kept but the rows."""
    import jax
    import jax.numpy as jnp

    nf, k = int(cfg["num_fields"]), int(cfg["v_dim"])
    B, F, W = rows.shape
    assert F == nf and W == 1 + nf * k, (rows.shape, nf, k)
    fields = jnp.arange(nf)

    def block(r):  # [b, F, W]
        v = r[..., 1:]

        def add_pairs_of(out, i):
            mine = jax.lax.dynamic_index_in_dim(v, i, axis=1, keepdims=False)  # v_{i,j}, all j
            theirs = jax.lax.dynamic_slice_in_dim(v, i * k, k, axis=2)  # v_{j,i}, all j
            pair = (mine.reshape(-1, nf, k) * theirs).sum(axis=-1)  # [b, j]
            later = jnp.where((fields > i)[None, :], pair, jnp.zeros((), r.dtype))
            return out + later.sum(axis=-1), None

        return jax.lax.scan(jax.checkpoint(add_pairs_of), r[..., 0].sum(axis=1), fields)[0]

    b = math.gcd(B, ROW_BLOCK)
    return jax.lax.map(jax.checkpoint(block), rows.reshape(B // b, b, F, W)).reshape(B)
