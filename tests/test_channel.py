"""Comm-channel layer unit contract (`repro.core.channel`) and the quant
round-trip hardening it builds on (`repro.quant.quantize_leaf`).

The substrate-level guarantees (identity == default bit-exact, ledger
integer-exact across all four substrates) live in tests/test_substrates.py;
this file pins the channel objects themselves: the static wire-byte math the
bytes ledger is priced with, the quantizer's checked edge cases (zero-size,
single-column, all-zero payloads), and the error-feedback recursion on the
broadcast link.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.channel import (
    CHANNELS,
    QUANT_BLOCK,
    get_channel,
    payload_nbytes,
    wire_vector_bytes,
)
from repro.quant import dequantize_leaf, quantize_leaf


# ------------------------------------------------------------ wire-byte math
def test_wire_bytes_identity_is_payload_bytes():
    assert wire_vector_bytes(None, 100, 4) == 400
    assert wire_vector_bytes("identity", 100, 8) == 800


def test_wire_bytes_cast_is_two_per_element():
    assert wire_vector_bytes("cast", 100, 4) == 200
    assert wire_vector_bytes("cast16", 100, 8) == 200


@pytest.mark.parametrize("d", [1, 255, 256, 257, 4096, 20_000_000])
def test_wire_bytes_quant8_closed_form(d):
    """int8 payload + one f32 scale per block, independent of input itemsize."""
    expected = d + 4 * math.ceil(d / QUANT_BLOCK)
    assert wire_vector_bytes("quant8", d, 4) == expected
    assert wire_vector_bytes("quant8", d, 8) == expected


def test_quant8_ratio_below_gate_at_large_d():
    """The benchmark gate (quant8 <= 0.27x float32) is a property of the wire
    format at large d: 1/4 + 4/(4*QUANT_BLOCK) = 0.2539 at block 256."""
    d = 4096
    ratio = wire_vector_bytes("quant8", d, 4) / wire_vector_bytes(None, d, 4)
    assert ratio <= 0.27


def test_payload_nbytes_prices_eval_shape_structs():
    """Pytree pricing works on ShapeDtypeStructs — real-model payloads are
    priced without allocating them (the example's qwen dry run)."""
    tree = {
        "w": jax.ShapeDtypeStruct((128, 64), jnp.float32),
        "b": jax.ShapeDtypeStruct((64,), jnp.bfloat16),
    }
    assert payload_nbytes(None, tree) == 128 * 64 * 4 + 64 * 2
    assert payload_nbytes("cast", tree) == (128 * 64 + 64) * 2
    q = wire_vector_bytes("quant8", 128 * 64) + wire_vector_bytes("quant8", 64)
    assert payload_nbytes("quant8", tree) == q


def test_get_channel_resolution():
    ident = get_channel(None)
    assert ident.name == "identity"
    assert get_channel("quant8") is CHANNELS["quant8"]
    assert get_channel(ident) is ident  # instance passthrough
    with pytest.raises(ValueError, match="unknown comm channel"):
        get_channel("zip9")


# ----------------------------------------------------- quantizer hardening
def test_quantize_leaf_roundtrip_zero_size():
    w = jnp.zeros((0, 8))
    out = dequantize_leaf(quantize_leaf(w))
    assert out.shape == w.shape


def test_quantize_leaf_roundtrip_one_column():
    w = jnp.asarray([[3.0], [-1.5], [0.25]])
    out = dequantize_leaf(quantize_leaf(w))
    np.testing.assert_allclose(np.asarray(out), np.asarray(w), rtol=1e-2)


def test_quantize_zero_payload_is_exact_zero():
    """All-zero channels must quantize to exact zeros (no 0/0 NaNs) — the
    property that lets quant8 commute with the client-sharded substrate's
    owner-masked zero rows."""
    out = dequantize_leaf(quantize_leaf(jnp.zeros((4, 300))))
    assert not np.any(np.asarray(out))
    assert np.all(np.isfinite(np.asarray(out)))


def test_quantize_leaf_rejects_malformed():
    with pytest.raises(TypeError, match="array leaf"):
        quantize_leaf([1.0, 2.0])
    with pytest.raises(ValueError, match="ndim"):
        quantize_leaf(jnp.asarray(1.0))
    with pytest.raises(TypeError, match="float"):
        quantize_leaf(jnp.arange(5))


def test_dequantize_rejects_malformed():
    with pytest.raises(TypeError, match="dict"):
        dequantize_leaf(jnp.zeros(3))
    with pytest.raises(TypeError, match="dict"):
        dequantize_leaf({"q": jnp.zeros(3, jnp.int8)})


# -------------------------------------------------------- channel behavior
def test_identity_channel_passthrough():
    ch = get_channel(None)
    v = jnp.linspace(-1, 1, 37)
    assert ch.up(v) is v
    state, sent = ch.down(ch.init_state(v), v)
    assert sent is v and state == ()


def test_cast_channel_roundtrip_precision():
    v = jnp.linspace(-3, 3, 1000, dtype=jnp.float32)
    out = get_channel("cast").up(v)
    assert out.dtype == v.dtype  # wire dtype round-trips back
    np.testing.assert_allclose(np.asarray(out), np.asarray(v), rtol=1e-2)


def test_quant8_up_error_bounded_per_block():
    """Blockwise symmetric int8: per-element error <= block amax / 127."""
    key = jax.random.key(0)
    v = jax.random.normal(key, (3, 1000))
    out = get_channel("quant8").up(v)
    blocks = 1000 // QUANT_BLOCK + 1
    pad = blocks * QUANT_BLOCK - 1000
    vp = np.pad(np.asarray(v), [(0, 0), (0, pad)]).reshape(3, blocks, QUANT_BLOCK)
    amax = np.abs(vp).max(-1, keepdims=True)
    bound = np.broadcast_to(amax / 127.0 + 1e-7, vp.shape).reshape(3, -1)[:, :1000]
    err = np.abs(np.asarray(out) - np.asarray(v))
    assert np.all(err <= bound)


def test_quant8_error_feedback_drives_bias_out():
    """EF on the broadcast link: transmitting the SAME vector repeatedly, the
    running mean of what was sent converges to the true vector — the
    accumulated residual corrects the deterministic quantization bias that a
    stateless link would repeat forever."""
    ch = get_channel("quant8")
    v = jax.random.normal(jax.random.key(1), (QUANT_BLOCK,)) * 0.1 + 2.0
    one_shot = float(np.abs(np.asarray(ch.up(v) - v)).max())
    state = ch.init_state(v)
    total = jnp.zeros_like(v)
    T = 64
    for _ in range(T):
        state, sent = ch.down(state, v)
        total = total + sent
    ef_err = float(np.abs(np.asarray(total / T - v)).max())
    assert ef_err < one_shot / 8


def test_quant8_is_deterministic_and_prng_free():
    """Same payload -> same wire output, no PRNG consumed: switching channels
    can never shift DP noise draws or client sampling."""
    ch = get_channel("quant8")
    v = jax.random.normal(jax.random.key(2), (777,))
    np.testing.assert_array_equal(np.asarray(ch.up(v)), np.asarray(ch.up(v)))


def test_quant8_long_rows_go_one_at_a_time_with_identical_numerics():
    """Rows of ROW_AT_A_TIME_MIN elements or more take the `lax.map` path;
    it must equal the batched blockwise codec bit for bit, and short rows
    must keep the batched form (no loop in their program)."""
    from repro.core import channel as C

    d = C.ROW_AT_A_TIME_MIN + 5  # not a whole number of blocks: the pad path
    v = jax.random.normal(jax.random.key(3), (2, 3, d))
    up = jax.jit(get_channel("quant8").up)
    np.testing.assert_array_equal(np.asarray(up(v)), np.asarray(jax.jit(C._roundtrip_blocks_int8)(v)))
    assert "scan" in str(jax.make_jaxpr(up)(v))
    short = v[..., :1000]
    assert "scan" not in str(jax.make_jaxpr(up)(short))
    np.testing.assert_array_equal(
        np.asarray(up(short)), np.stack([np.asarray(up(r)) for r in short.reshape(6, 1000)]).reshape(short.shape)
    )
