"""Pallas TPU kernel: word-packed OR-scatter — the visited/rare-list setter.

jnp has no OR-scatter, so the byte-per-slot bitmap tables (search visited
set, merged rare-list table) historically stayed bool to keep `.at[].set`
usable. This kernel ORs bit ``1 << (slot & 31)`` into word ``slot >> 5`` of
an int32 word table, letting those tables shrink 32× (8× vs bool bytes on
host, 32× vs the int8 lanes bools occupy on TPU) before they get multiplied
by shard-replicated query state.

Words are int32, not uint32: TPU vector lanes are signed and the rest of the
repo already bitcasts its uint32 bit-words to int32 at the kernel boundary
(see selectors.kernel_view). Shifts are defined modulo the word width, and
``(w >> k) & 1`` extracts bits correctly even for the sign bit, so signed
words are bitwise-equivalent for set/test.

One program per batch row, walking the row's C slots in order. The slots
sit in scalar memory; each in-range slot reads one (8, 128) word tile of
the row from VMEM, ORs its bit in through a one-hot lane/sublane select,
and writes the tile back, so a call costs O(C) tile updates whatever the
table width. The word table is laid out (B, NW/1024, 8, 128) for it (NW
pads to whole tiles): a dynamic index on a leading axis is what the chip
loads and stores cheaply. Duplicate slots and already-set bits are
naturally idempotent. Out-of-range slots (< 0 or >= NW*32) contribute
nothing — callers encode "skip this lane" as any such sentinel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_TILE_WORDS = 8 * 128


def _or_scatter_kernel(slots_ref, words_ref, out_ref, *, n_bits: int):
    out_ref[...] = words_ref[...]
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)

    def body(j, carry):
        s = slots_ref[0, 0, j]

        @pl.when((s >= 0) & (s < n_bits))
        def _():
            w = s >> 5
            tile = w // _TILE_WORDS
            r = w % _TILE_WORDS
            hit = (sub == r // 128) & (lane == r % 128)
            bit = jax.lax.shift_left(jnp.int32(1), s & 31)
            out_ref[0, tile] = out_ref[0, tile] | jnp.where(hit, bit, 0)

        return carry

    jax.lax.fori_loop(0, slots_ref.shape[2], body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def or_scatter(words: jax.Array, slots: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """Row-wise bitmap OR-scatter. words (B, NW) int32 bit-words; slots
    (B, C) int32 bit indices into [0, NW*32) — out-of-range lanes are
    dropped. Returns words with bit ``slots[b, j]`` set for every in-range
    slot of row b."""
    b, nw = words.shape
    c = slots.shape[1]
    n_tiles = -(-nw // _TILE_WORDS)
    tiles = jnp.pad(words.astype(jnp.int32),
                    ((0, 0), (0, n_tiles * _TILE_WORDS - nw)))
    tiles = tiles.reshape(b, n_tiles, 8, 128)
    block = pl.BlockSpec((1, n_tiles, 8, 128), lambda i: (i, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_or_scatter_kernel, n_bits=nw * 32),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM),
                  block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.int32),
        interpret=interpret,
    )(slots.astype(jnp.int32).reshape(b, 1, c), tiles)
    return out.reshape(b, n_tiles * _TILE_WORDS)[:, :nw]
