"""The Mamba-2 mixer (``models/ssm.py``) and its two kernels
(``ops/ssm_scan.py``, under the interpreter) against the TOKEN-BY-TOKEN
recurrence of the plain reference
(``benchmark/families/nemotron_h_reference.py::_mamba``): a whole sequence
through the chunked scan, chunks with a carried state, ragged
``chunk_lengths``, a frozen decode row, rows chained inside one call. Each
tolerance carries its reason.
"""

import functools
import pathlib
import sys
from unittest import mock

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark.families.nemotron_h_reference import _mamba  # noqa: E402
from learning_jax_sharding_tpu.models.ssm import Mamba2Mixer  # noqa: E402
from learning_jax_sharding_tpu.ops import ssm_scan  # noqa: E402

#: float32 program against the float32 reference: the chunked form sums in
#: another order than the token-by-token scan; the largest departure
#: measured over these cases was 4e-6 on outputs of magnitude 1.
F32_TOL = 2e-5
#: What a state kept in bf16 has to exceed: 8 bits of mantissa re-rounded at
#: every one of 96 steps read 4e-3 and more here.
BF16_STATE_FLOOR = 1e-3

DIMS = {
    "ssm_heads": 4, "ssm_head_dim": 16, "ssm_groups": 2, "ssm_state": 16,
    "conv_kernel": 4, "norm_eps": 1e-5,
}
CHUNK = 8


def _mixer(**kw):
    args = dict(
        features=32, num_heads=4, head_dim=16, groups=2, state_size=16,
        chunk=CHUNK, norm_eps=1e-5,
    )
    args.update(kw)
    return Mamba2Mixer(**args)


@functools.cache
def _apply(mixer, ragged, backend="xla"):
    """``mixer.apply`` with a mutable cache, jitted once a mixer and
    ``backend``: the cached forms are ``models/ssm.py``'s XLA ops or, under
    the interpreter, the kernels (no option chooses: the test stands in for
    ``resolve_backend``, which picks the kernels on a TPU alone)."""

    def run(params, cache, x, lengths):
        variables = {"params": params, **({"cache": cache} if cache else {})}
        return mixer.apply(
            variables, x, mutable=("cache",),
            chunk_lengths=lengths if ragged else None,
        )

    jitted = jax.jit(run)

    def call(*args):
        with mock.patch.object(ssm_scan, "resolve_backend", lambda **_: backend):
            return jitted(*args)

    return call


_reference = jax.jit(lambda x, params: _mamba(x, params, DIMS))


def _setup(seed=0, batch=2, length=29):
    x = jax.random.normal(jax.random.key(seed), (batch, length, 32))
    params = nn.meta.unbox(_mixer().init(jax.random.key(seed + 1), x))["params"]
    # The convolution's bias starts at zero; give the reference's term work.
    params["conv"]["bias"] = 0.1 * jax.random.normal(
        jax.random.key(seed + 2), params["conv"]["bias"].shape
    )
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_reference(x, params))
    return x, params, want


def _stream(mixer, params, x, pieces, lengths=None, backend="xla"):
    """``x`` through a decode-mode mixer a piece at a time (``pieces``: the
    widths), the cache carried; ``lengths[i]`` ``(B,)``: piece i's valid
    counts (the rest is padding the caller has put there)."""
    cache, outs, at = None, [], 0
    for i, width in enumerate(pieces):
        out, mut = _apply(mixer, lengths is not None, backend)(
            params, cache, x[:, at:at + width],
            None if lengths is None else jnp.asarray(lengths[i]),
        )
        cache, at = mut["cache"], at + width
        outs.append(np.asarray(out))
    return np.concatenate(outs, axis=1), cache


def test_published_initialisers_spread_the_time_constants():
    _, params, _ = _setup()
    a = np.exp(np.asarray(params["A_log"]))
    dt = np.log1p(np.exp(np.asarray(params["dt_bias"])))        # softplus
    assert (a >= 1).all() and (a <= 16).all()
    assert (dt >= 1e-3 - 1e-6).all() and (dt <= 0.1 + 1e-6).all()
    np.testing.assert_array_equal(np.asarray(params["D"]), 1.0)
    assert set(params) == {"in_proj", "conv", "dt_bias", "A_log", "D", "norm", "out_proj"}


def test_whole_sequence_matches_the_token_by_token_recurrence():
    # 29 tokens in tiles of 8: three whole tiles and a padded one.
    x, params, want = _setup()
    got = _mixer().apply({"params": params}, x)
    assert np.abs(np.asarray(got) - want).max() < F32_TOL


@pytest.mark.parametrize("scan", ["xla", "pallas"])
def test_chunks_then_single_tokens_carry_the_state(scan):
    x, params, want = _setup(seed=3)
    mixer = _mixer(decode=True)
    got, cache = _stream(mixer, params, x, [CHUNK, CHUNK, CHUNK] + [1] * 5, backend=scan)
    assert np.abs(got - want).max() < F32_TOL
    assert cache["ssm_state"].dtype == jnp.float32
    assert cache["conv_state"].shape == (2, 3, 64 + 2 * 2 * 16)
    np.testing.assert_array_equal(np.asarray(cache["carry_from"]), -1)


@pytest.mark.parametrize("scan", ["xla", "pallas"])
def test_ragged_chunks_and_a_frozen_decode_row(scan):
    # Row 0 takes 8 + 8 + 5 tokens and then steps; row 1 takes 8 + 3, sits
    # out a chunk and two steps frozen (length 0), then steps on: padding
    # and frozen steps must leave its state and convolution inputs alone.
    x, params, want = _setup(seed=5, length=24)
    mixer = _mixer(decode=True)
    row0 = [(0, 8), (8, 8), (16, 5), (21, 1), (22, 1), (23, 1)]
    row1 = [(0, 8), (8, 3), (11, 0), (11, 0), (11, 0), (11, 1), (12, 1)]
    cache, got = None, [np.zeros((24, 32)), np.zeros((24, 32))]
    for step in range(7):
        width = CHUNK if step < 3 else 1
        piece, lens = np.zeros((2, width, 32), np.float32), [0, 0]
        for r, plan in enumerate((row0, row1)):
            if step < len(plan):
                at, n = plan[step]
                piece[r, :n], lens[r] = x[r, at:at + n], n
                if n == 0:
                    piece[r] = 7.0          # garbage a frozen row must ignore
        out, mut = _apply(mixer, True, scan)(
            params, cache, jnp.asarray(piece), jnp.asarray(lens)
        )
        before, cache = cache, mut["cache"]
        for r, plan in enumerate((row0, row1)):
            if step < len(plan) and plan[step][1]:
                at, n = plan[step]
                got[r][at:at + n] = np.asarray(out[r, :n])
            elif before is not None:
                for leaf in ("ssm_state", "conv_state"):
                    np.testing.assert_array_equal(
                        np.asarray(cache[leaf][r]), np.asarray(before[leaf][r])
                    )
    assert np.abs(got[0] - want[0]).max() < F32_TOL
    assert np.abs(got[1][:13] - want[1][:13]).max() < F32_TOL


def test_a_state_kept_in_bf16_fails_the_tolerance():
    x, params, want = _setup(seed=7, batch=1, length=96)
    pieces = [CHUNK] * 4 + [1] * 64
    got, _ = _stream(_mixer(decode=True), params, x, pieces)
    low, cache = _stream(
        _mixer(decode=True, state_dtype=jnp.bfloat16), params, x, pieces
    )
    assert cache["ssm_state"].dtype == jnp.bfloat16
    assert np.abs(got - want).max() < F32_TOL
    assert np.abs(low - want).max() > BF16_STATE_FLOOR


# --- rows chained inside one call, and the kernels against the XLA forms --------


def _chained(scan, seed=11):
    """Three chunks of one sequence as rows 2, 0, 3 of ONE call (row 1 is
    another sequence's single chunk), each told the row it continues."""
    x, params, want = _setup(seed=seed, batch=2, length=3 * CHUNK)
    mixer = _mixer(decode=True)
    zero = jnp.zeros((4, CHUNK, 32))
    _, mut = _apply(mixer, True, scan)(params, None, zero, jnp.zeros((4,), jnp.int32))
    cache = dict(mut["cache"])
    cache["carry_from"] = jnp.asarray([2, -1, -1, 0], jnp.int32)
    rows = jnp.stack([x[0, 8:16], x[1, :8], x[0, :8], x[0, 16:24]])
    out, mut = _apply(mixer, True, scan)(params, cache, rows, jnp.asarray([8, 5, 8, 8]))
    return np.asarray(out), mut["cache"], want


@pytest.mark.parametrize("scan", ["xla", "pallas"])
def test_rows_of_one_call_continue_one_another(scan):
    out, cache, want = _chained(scan)
    got = np.concatenate([out[2], out[0], out[3]])
    assert np.abs(got - want[0]).max() < F32_TOL
    assert np.abs(out[1][:5] - want[1][:5]).max() < F32_TOL
    assert cache["ssm_state"].shape == (4, 2, 16, 32)        # heads in pairs


def test_the_kernels_match_the_xla_forms():
    out_x, cache_x, _ = _chained("xla")
    out_p, cache_p, _ = _chained("pallas")
    assert np.abs(out_x - out_p).max() < F32_TOL
    for leaf in ("ssm_state", "conv_state"):
        assert np.abs(np.asarray(cache_x[leaf]) - np.asarray(cache_p[leaf])).max() < F32_TOL


def test_chain_order_puts_every_chain_in_sequence():
    # Rows 4 -> 1 -> 3 chain (4 first), 0 -> 2, row 5 alone.
    carry_from = jnp.asarray([-1, 4, 0, 1, -1, -1], jnp.int32)
    order, carried = ssm_scan.chain_order(carry_from)
    order, carried = np.asarray(order).tolist(), np.asarray(carried).tolist()
    assert sorted(order) == list(range(6))
    for row, src in enumerate(np.asarray(carry_from).tolist()):
        at = order.index(row)
        assert carried[at] == (src >= 0)
        if src >= 0:
            assert order[at - 1] == src


def test_state_update_kernel_is_in_place_arithmetic():
    groups, heads = 2, 4
    key = jax.random.split(jax.random.key(0), 5)
    h = jax.random.normal(key[0], (3, heads, 16, 16))
    decay = jax.random.uniform(key[1], (3, heads))
    du = jax.random.normal(key[2], (3, heads, 16))
    bm = jax.random.normal(key[3], (3, groups, 16))
    cm = jax.random.normal(key[4], (3, groups, 16))
    # Row 1 is frozen: decay 1, no input.
    decay, du = decay.at[1].set(1.0), du.at[1].set(0.0)
    packed, y = ssm_scan.state_update(
        ssm_scan.pack_state(h), decay, du, bm, cm, interpret=True
    )
    h_new = ssm_scan.unpack_state(packed)
    b_h, c_h = jnp.repeat(bm, 2, axis=1), jnp.repeat(cm, 2, axis=1)
    want = h * decay[..., None, None] + du[..., None] * b_h[:, :, None, :]
    assert np.abs(np.asarray(h_new) - np.asarray(want)).max() < 1e-6
    assert np.abs(np.asarray(y) - np.asarray(jnp.sum(want * c_h[:, :, None, :], -1))).max() < 1e-5
    np.testing.assert_array_equal(np.asarray(h_new[1]), np.asarray(h[1]))


@pytest.mark.parametrize(
    "platform, shapes, want",
    [
        ("tpu", dict(p=64, n=128), "pallas"),
        ("tpu", dict(p=64, n=128, q=128), "pallas"),
        ("tpu", dict(p=64, n=128, q=64), "xla"),         # a tile of half a lane tile
        ("tpu", dict(p=16, n=16), "xla"),                # the tests' sizes
        ("cpu", dict(p=64, n=128, q=128), "xla"),
    ],
)
def test_platform_and_shape_alone_pick_the_kernels(platform, shapes, want):
    with mock.patch.object(jax, "default_backend", lambda: platform):
        assert ssm_scan.resolve_backend(**shapes) == want
