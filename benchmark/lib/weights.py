"""Initial weights from the seed, addressable by element.

A latent weight is a function of (seed, slot, column) alone: integer
hashing and one float multiply, so NumPy on the host and XLA on the
device give the same bits. The program's table is filled on the device
in one jitted call; the reference evaluates the same function at the
slots it touches and never sees the program's table.

The draw is the sum of four 16-bit uniforms, centred and scaled to unit
variance (Irwin-Hall, n=4: bell-shaped, bounded at 3.46 sd), times the
configuration's `v_init_scale`: the reference's N(0,1)*1e-2 to two
moments, without a transcendental that two back ends would round
differently.
"""

from __future__ import annotations

import numpy as np

from .traffic import _fmix32, seed_words

# sd of a sum of four uniforms on {0..65535}
_UNIT = float(1.0 / np.sqrt(4.0 * ((1 << 32) - 1) / 12.0))


def _draw(xp, counter, s1, s2, scale):
    """counter: uint32 array (slot * width + column) -> float32 ~ (0,
    scale): an exact integer times ONE float32 constant, so no back end
    can regroup the arithmetic."""
    u32 = xp.uint32
    a, b = _fmix32(counter ^ u32(s1)), _fmix32(counter ^ u32(s2))
    total = (
        (a >> u32(16)).astype(xp.int32) + (a & u32(0xFFFF)).astype(xp.int32)
        + (b >> u32(16)).astype(xp.int32) + (b & u32(0xFFFF)).astype(xp.int32)
        - xp.int32(2 * 65535)
    )
    return total.astype(xp.float32) * xp.float32(_UNIT * scale)


def rows_numpy(seed: int, slots: np.ndarray, width: int, scale: float) -> np.ndarray:
    """float32 [len(slots), width]: column 0 (the linear weight) is 0,
    columns 1.. are latent draws."""
    s1, s2 = seed_words(seed)
    counter = (slots.astype(np.uint32)[:, None] * np.uint32(width)
               + np.arange(width, dtype=np.uint32)[None, :])
    with np.errstate(over="ignore"):
        out = _draw(np, counter, s1, s2, scale)
    out[:, 0] = 0.0
    return out


def packed_table_fn(seed: int, num_slots: int, width: int, pack: int, scale: float):
    """() -> float32 [num_slots / pack, pack * width] in the packed
    layout (slot s at row s // pack, columns (s % pack) * width + j),
    for jax.jit with the table's sharding as out_shardings."""
    import jax.numpy as jnp

    s1, s2 = seed_words(seed)

    def make():
        r = jnp.arange(num_slots // pack, dtype=jnp.uint32)[:, None]
        q = jnp.arange(pack * width, dtype=jnp.uint32)[None, :]
        # slot * width + column == r * pack * width + q
        vals = _draw(jnp, r * jnp.uint32(pack * width) + q, s1, s2, scale)
        return jnp.where(q % jnp.uint32(width) == 0, jnp.float32(0.0), vals)

    return make


def chunk_table_fn(seed: int, num_slots: int, width: int, scale: float):
    """(first_slot) -> float32 [num_slots, width], the logical rows of
    slots first_slot .. first_slot + num_slots: one jitted program
    for every chunk of a table that is made on the way to the host."""
    import jax.numpy as jnp

    s1, s2 = seed_words(seed)

    def make(first_slot):
        r = first_slot.astype(jnp.uint32) + jnp.arange(num_slots, dtype=jnp.uint32)[:, None]
        q = jnp.arange(width, dtype=jnp.uint32)[None, :]
        vals = _draw(jnp, r * jnp.uint32(width) + q, s1, s2, scale)
        return jnp.where(q == 0, jnp.float32(0.0), vals)

    return make
