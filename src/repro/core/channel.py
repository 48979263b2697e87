"""Comm channels: the client<->server wire as a first-class, pluggable layer.

Every transfer in a federated round — the iterate broadcast to the sampled
cohort, the prox results coming back, the anchor broadcast on a refresh
event — flows through ONE of these channel objects, injected into the round
substrate (`repro.core.rounds.RoundOps`) as static configuration
(``run_batch(..., channel="quant8")``).  The round definitions stay
channel-agnostic: they call ``ops.chan_down`` / ``ops.chan_up`` /
``ops.chan_bcast`` at the transfer seams and the bound channel decides what
the wire does to the payload.

==========  =================================================================
channel     wire behavior
==========  =================================================================
identity    nothing — bit-exact passthrough, zero state.  The default; every
            pre-channel trajectory is reproduced exactly.
quant8      blockwise symmetric int8 (block ``QUANT_BLOCK`` along the payload
            axis, one f32 scale per block — `repro.quant.quantize_leaf` /
            `dequantize_leaf` on the blocked view).  The server->client
            iterate broadcast carries EF21-style ERROR FEEDBACK: the channel
            state accumulates the quantization residual ``e`` and transmits
            ``Q(v + e)``, so the compression error is corrected over rounds
            instead of compounding.  Client->server and anchor links are
            stateless quantize->dequantize.
cast        bf16 wire dtype (stateless round-trip cast).
cast16      fp16 wire dtype.
==========  =================================================================

Bytes accounting
----------------
``wire_nbytes(size, itemsize)`` prices one payload of ``size`` elements on
the wire, as a static python int computed from the payload shape and the
channel's wire dtype:

* identity: ``size * itemsize`` (the payload's own dtype);
* cast/cast16: ``size * 2``;
* quant8: ``size`` int8 bytes + one f32 scale per ``QUANT_BLOCK`` block,
  ``size + 4 * ceil(size / QUANT_BLOCK)`` — a 0.254x ratio vs f32 at
  block 256.

`payload_nbytes` prices an arbitrary PYTREE payload (arrays or
`jax.ShapeDtypeStruct` leaves, so `jax.eval_shape` dry-runs price real model
shapes without allocating them) by summing ``wire_nbytes`` over leaves.

Error feedback state is replicated per-trial state (never sharded), so the
same channel binding runs unchanged on all four substrates; quantization is
deterministic and consumes no PRNG keys, so DP noise draws and client
sampling are untouched by switching channels.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.quant.quant import dequantize_leaf, quantize_leaf

#: Block length for quant8's blockwise scales.  256 keeps the scale overhead
#: at 4/(256+4) ~ 1.5% of the wire while bounding per-block dynamic range.
QUANT_BLOCK = 256


class CommChannel:
    """Identity channel — and the interface every channel implements.

    Payloads are pytrees whose leaves carry the transferred vector along the
    LAST axis (leading axes are trial/cohort/client rows and are compressed
    row-independently, so batched substrates reproduce the sequential
    per-row results bit-for-bit).

    * ``init_state(payload) -> state`` — per-run channel state (EF residual),
      shaped like the broadcast payload; ``()`` for stateless channels;
    * ``down(state, v) -> (state, v_hat)`` — server->client broadcast, the
      one link that may carry state;
    * ``up(v) -> v_hat`` — client->server, stateless;
    * ``bcast(v) -> v_hat`` — anchor broadcast on refresh events, stateless;
    * ``wire_nbytes(size, itemsize) -> int`` — static bytes for one payload.
    """

    name = "identity"
    stateful = False

    def wire_nbytes(self, size: int, itemsize: int = 4) -> int:
        return int(size) * int(itemsize)

    def init_state(self, payload):
        return ()

    def up(self, v):
        return v

    def bcast(self, v):
        return self.up(v)

    def down(self, state, v):
        return state, self.up(v)


class CastChannel(CommChannel):
    """Round-trip the payload through a reduced wire dtype (bf16/fp16)."""

    def __init__(self, name: str, wire_dtype):
        self.name = name
        self.wire_dtype = jnp.dtype(wire_dtype)

    def wire_nbytes(self, size: int, itemsize: int = 4) -> int:
        return int(size) * self.wire_dtype.itemsize

    def up(self, v):
        return jax.tree.map(
            lambda a: a.astype(self.wire_dtype).astype(a.dtype), v
        )


#: Payload rows at least this long are quantized one row at a time.
#: Padding the last axis of a `(rows, d)` array to whole blocks is a relayout
#: on a TPU whose generated code grows with `d`: compiled for a v5e, a
#: `(4, 2**22 + 1)` float32 payload takes 18 s and 14 MB of code batched and
#: 1.9 s and 2.6 MB row by row, while at `(32, 2**18 + 1)` the batched form
#: compiles in under a second.
ROW_AT_A_TIME_MIN = 1 << 20


def _roundtrip_blocks_int8(a):
    """Blockwise int8 quantize->dequantize along the last axis of `a`."""
    d = a.shape[-1]
    nb = -(-d // QUANT_BLOCK)
    pad = nb * QUANT_BLOCK - d
    if pad:
        a_p = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    else:
        a_p = a
    blocks = a_p.reshape(a.shape[:-1] + (nb, QUANT_BLOCK))
    deq = dequantize_leaf(quantize_leaf(blocks, reduce_axis=-1), a.dtype)
    return deq.reshape(a.shape[:-1] + (nb * QUANT_BLOCK,))[..., :d]


def _roundtrip_block_int8(a):
    """Blockwise int8 quantize->dequantize along the last axis of one leaf;
    rows of `ROW_AT_A_TIME_MIN` elements or more go one at a time
    (`lax.map`)."""
    d = a.shape[-1]
    if d == 0:
        return a
    if a.ndim == 1 or d < ROW_AT_A_TIME_MIN:
        return _roundtrip_blocks_int8(a)
    rows = jax.lax.map(_roundtrip_blocks_int8, a.reshape(-1, d))
    return rows.reshape(a.shape)


class Quant8Channel(CommChannel):
    """Blockwise symmetric int8 wire, error feedback on the broadcast link.

    ``down`` transmits ``Q(v + e)`` and carries ``e' = v + e - Q(v + e)``:
    the standard EF21-style residual correction, so the broadcast link's
    compression error is driven out over rounds.  Zero payloads quantize to
    exact zeros (`quantize_leaf` guards the zero scale), which is what makes
    the channel commute with the client-sharded substrate's owner-masked
    zero rows.
    """

    name = "quant8"
    stateful = True

    def wire_nbytes(self, size: int, itemsize: int = 4) -> int:
        size = int(size)
        return size + 4 * math.ceil(size / QUANT_BLOCK)

    def init_state(self, payload):
        return jax.tree.map(jnp.zeros_like, payload)

    def up(self, v):
        return jax.tree.map(_roundtrip_block_int8, v)

    def down(self, state, v):
        corrected = jax.tree.map(jnp.add, v, state)
        sent = self.up(corrected)
        residual = jax.tree.map(jnp.subtract, corrected, sent)
        return residual, sent


IDENTITY = CommChannel()

CHANNELS: dict[str, CommChannel] = {
    "identity": IDENTITY,
    "quant8": Quant8Channel(),
    "cast": CastChannel("cast", jnp.bfloat16),
    "cast16": CastChannel("cast16", jnp.float16),
}


def get_channel(channel) -> CommChannel:
    """Resolve a channel spec (None / name / instance) to a `CommChannel`."""
    if channel is None:
        return IDENTITY
    if isinstance(channel, CommChannel):
        return channel
    try:
        return CHANNELS[channel]
    except KeyError:
        raise ValueError(
            f"unknown comm channel {channel!r}: expected one of "
            f"{sorted(CHANNELS)} (or None for identity)"
        ) from None


def wire_vector_bytes(channel, size: int, itemsize: int = 4) -> int:
    """Static wire bytes for ONE d-vector payload under a channel."""
    return get_channel(channel).wire_nbytes(size, itemsize)


def payload_nbytes(channel, payload) -> int:
    """Static wire bytes for a pytree payload (arrays or ShapeDtypeStructs).

    Computed from leaf shapes x the channel's wire dtype only — safe on
    `jax.eval_shape` outputs, so real-model payloads are priced without
    allocating them.
    """
    ch = get_channel(channel)
    return sum(
        ch.wire_nbytes(math.prod(leaf.shape), jnp.dtype(leaf.dtype).itemsize)
        for leaf in jax.tree.leaves(payload)
    )
