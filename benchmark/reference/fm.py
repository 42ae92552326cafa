"""Factorization machine (Rendle 2010), one table row [w, v_1..v_k] per
feature, binary features:
    logit = sum_i w_i + 1/2 sum_k [ (sum_i v_ik)^2 - sum_i v_ik^2 ]
"""


def width(cfg: dict) -> int:
    return 1 + int(cfg["v_dim"])


def leaves(cfg: dict) -> dict:
    return {"w": slice(0, 1), "v": slice(1, width(cfg))}


def logits(rows, cfg: dict):
    """rows [B, F, 1 + k] -> [B]."""
    wx = rows[..., 0].sum(axis=1)
    v = rows[..., 1:]
    s = v.sum(axis=1)
    q = (v * v).sum(axis=1)
    return wx + 0.5 * (s * s - q).sum(axis=-1)
