"""Sparse logistic regression, binary features: logit = sum_i w_i."""


def width(cfg: dict) -> int:
    return 1


def leaves(cfg: dict) -> dict:
    return {"w": slice(0, 1)}


def logits(rows, cfg: dict):
    """rows [B, F, 1] -> [B]."""
    return rows[..., 0].sum(axis=1)
