"""The major-axis shift helpers of ``rowconv/xpack.py`` against the
minor-axis ones they twin: the same words, transposed, at the widths the
tiled programs use (string windows of 4–12 words, chars frames of 8–130,
group stretches of 72–88), for shifts at 0, at the last word and at every
byte offset, string counts off the lane width, and zero-length strings."""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_jni_tpu.rowconv import xpack

RNG = np.random.default_rng(33)


def words(*shape):
    return jnp.asarray(RNG.integers(0, 1 << 32, shape, dtype=np.uint32))


def shifts(n, last):
    """0, the last word that fits, and a draw between them."""
    sh = RNG.integers(0, last + 1, n, dtype=np.int32)
    sh[0::7] = 0
    sh[3::7] = last
    return jnp.asarray(sh)


def as_major(x, rest):
    """[n, W] → [W, *rest]."""
    return x.T.reshape((x.shape[1],) + rest)


def assert_twin(minor, major):
    np.testing.assert_array_equal(
        np.asarray(minor).T, np.asarray(major).reshape(major.shape[0], -1))


# string counts: under a lane, off the lane width, and as the tiles shape
# them ([GROUP, groups]) with a ragged lane count
RESTS = [(37,), (200,), (8, 45)]


@pytest.mark.parametrize("rest", RESTS)
@pytest.mark.parametrize("W,Wo", [(8, 5), (16, 9), (98, 11), (128, 9),
                                  (130, 13), (64, 7)])
def test_take_words_major(W, Wo, rest):
    n = int(np.prod(rest))
    m, sh = words(n, W), shifts(n, W - Wo)
    assert_twin(xpack._take_words(m, sh, Wo),
                xpack._take_words_major(as_major(m, rest), sh.reshape(rest),
                                        Wo))


def test_take_words_major_past_the_source_reads_zeros():
    m, sh = words(50, 20), jnp.asarray(RNG.integers(0, 40, 50,
                                                    dtype=np.int32))
    got = xpack._take_words_major(m.T, sh, 6)
    assert_twin(xpack._take_words(m, sh, 6), got)
    assert not np.asarray(got)[:, np.asarray(sh) >= 20].any()


def test_take_words_major_one_source_serves_several_strings():
    # the tiled from_rows: a row's frame broadcast over its string columns
    m = words(24, 98)
    sh = shifts(5 * 24, 98 - 11).reshape(5, 24)
    got = xpack._take_words_major(m.T[:, None], sh, 11)          # [11, 5, 24]
    for k in range(5):
        assert_twin(xpack._take_words(m, sh[k], 11), got[:, k])


@pytest.mark.parametrize("rest", RESTS)
@pytest.mark.parametrize("Wi,Wo", [(5, 8), (9, 98), (11, 80), (13, 130),
                                   (11, 72), (9, 88)])
def test_place_words_major(Wi, Wo, rest):
    n = int(np.prod(rest))
    m, sh = words(n, Wi), shifts(n, Wo - Wi)
    assert_twin(xpack._place_words(m, sh, Wo),
                xpack._place_words_major(as_major(m, rest), sh.reshape(rest),
                                         Wo))


@pytest.mark.parametrize("rest", RESTS)
@pytest.mark.parametrize("Lw", [4, 8, 10, 12])
def test_roll_and_funnel_major_at_every_byte_offset(Lw, rest):
    n = int(np.prod(rest))
    rb = jnp.asarray(np.arange(n, dtype=np.int32) % 4)
    w = words(n, Lw + 1)
    assert_twin(xpack._roll_left_bytes(w, Lw, rb),
                xpack._roll_left_bytes_major(as_major(w, rest), Lw,
                                             rb.reshape(rest)))
    assert_twin(xpack._byte_funnel_right(w, rb),
                xpack._byte_funnel_right_major(as_major(w, rest),
                                               rb.reshape(rest)))


@pytest.mark.parametrize("rest", RESTS)
@pytest.mark.parametrize("W", [4, 10, 80])
def test_byte_mask_major(W, rest):
    n = int(np.prod(rest))
    start = RNG.integers(0, W * 4 + 1, n, dtype=np.int32)
    length = RNG.integers(0, 33, n, dtype=np.int32)
    length[::5] = 0                                   # zero-length strings
    start[1::5] = 0
    start, end = jnp.asarray(start), jnp.asarray(start + length)
    got = xpack._byte_mask_major(W, start.reshape(rest), end.reshape(rest))
    assert_twin(xpack._byte_mask(W, start, end), got)
    assert not np.asarray(got).reshape(W, -1)[:, ::5].any()


@pytest.mark.parametrize("rest", RESTS)
@pytest.mark.parametrize("Lw,Cw", [(4, 8), (8, 98), (12, 130)])
def test_string_chain_major(Lw, Cw, rest):
    """A tile's whole per-string chain, both ways round: cut a string out
    of a wide source, mask it to its length, put it at a byte of a frame."""
    n = int(np.prod(rest))
    src = words(n, 2 * Lw + 8)
    at = jnp.asarray(RNG.integers(0, (Lw + 7) * 4, n, dtype=np.int32))
    ln = RNG.integers(0, Lw * 4 + 1, n, dtype=np.int32)
    ln[::4] = 0
    ln = jnp.asarray(ln)
    pos = jnp.asarray(RNG.integers(0, (Cw - Lw - 1) * 4 + 1, n,
                                   dtype=np.int32))

    piece = xpack._roll_left_bytes(
        xpack._take_words(src, at // 4, Lw + 1), Lw, at % 4)
    piece = piece & xpack._byte_mask(Lw, jnp.zeros_like(ln), ln)
    minor = xpack._place_words(xpack._byte_funnel_right(piece, pos % 4),
                               pos // 4, Cw)

    piece = xpack._cut_strings_major(as_major(src, rest), at.reshape(rest),
                                     Lw)
    major = xpack._pin_words_major(xpack._put_strings_major(
        piece, ln.reshape(rest), pos.reshape(rest), Cw))
    assert_twin(minor, major)
