"""The window of `ops/attention.flash_attention` (a sliding-window layer's
band: query i sees keys i - W + 1 .. i) and the YaRN rotary tables of
`layers/decoder.py`.

The interpreted kernels are held to `attention(..., window=W)` at the bounds
the causal kernels meet in `test_attention.py` (float32, sums in another
order alone); the loop bounds are held to the band tile by tile; and with no
window the kernels' bodies are what they were before the window came."""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.layers import decoder
from tensor2robot_tpu.ops import attention as attn


def _qkv(t, h, d, seed=0):
  keys = jax.random.split(jax.random.PRNGKey(seed), 4)
  return tuple(jax.random.normal(k, (1, t, h * d)) for k in keys)


def _reference(q, k, v, h, window):
  b, t, hd = q.shape
  heads = lambda x: x.reshape(b, t, h, hd // h).transpose(0, 2, 1, 3)  # noqa
  out = attn.attention(heads(q), heads(k), heads(v), causal=True,
                       window=window)
  return out.transpose(0, 2, 1, 3).reshape(b, t, hd)


# (T, heads, head_dim, window, block_q, block_k): W of 1, 16 and one no
# block divides; T that pads; heads of 32 (four a program) and of 128 (one a
# program). A window at or past T runs the causal kernels (below).
CASES = [
    (64, 2, 32, 1, 16, 16),
    (200, 4, 32, 23, 64, 32),     # pads to 256; 23 divides no block
    (96, 1, 128, 16, 32, 64),     # pads to 128
]


@pytest.mark.parametrize("t,h,d,window,bq,bk", CASES)
def test_window_matches_the_band_mask(t, h, d, window, bq, bk):
  q, k, v, probe = _qkv(t, h, d, seed=t + window)
  flash = jax.jit(lambda q, k, v: attn.flash_attention(
      q, k, v, h, causal=True, window=window, block_q=bq, block_k=bk,
      interpret=True))
  ref = lambda q, k, v: _reference(q, k, v, h, window)  # noqa: E731
  np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                             np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5)
  grads = [jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * probe),
                            argnums=(0, 1, 2)))(q, k, v) for f in (flash, ref)]
  for got, want in zip(*grads):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=5e-4)


def test_window_masks_what_lies_below_the_band():
  """A key W or more behind a row does not reach it, one W - 1 behind does."""
  t, h, d, window = 64, 1, 128, 8
  q, k, v, _ = _qkv(t, h, d)
  out = attn.flash_attention(q, k, v, h, causal=True, window=window,
                             block_q=16, block_k=16, interpret=True)
  far = v.at[:, 40 - window].add(100.0)
  near = v.at[:, 40 - window + 1].add(100.0)
  run = lambda vv: attn.flash_attention(  # noqa: E731
      q, k, vv, h, causal=True, window=window, block_q=16, block_k=16,
      interpret=True)
  np.testing.assert_array_equal(np.asarray(run(far)[:, 40]),
                                np.asarray(out[:, 40]))
  assert float(jnp.max(jnp.abs(run(near)[:, 40] - out[:, 40]))) > 1e-3


def test_a_window_that_reaches_every_key_is_causal():
  q, k, v, _ = _qkv(64, 2, 32)
  for window in (64, 1000):
    np.testing.assert_array_equal(
        np.asarray(attn.flash_attention(q, k, v, 2, causal=True,
                                        window=window, interpret=True)),
        np.asarray(attn.flash_attention(q, k, v, 2, causal=True,
                                        interpret=True)))


def test_a_window_needs_causal_attention_and_a_key():
  q, k, v, _ = _qkv(32, 1, 128)
  for causal, window in ((False, 8), (True, 0)):
    with pytest.raises(ValueError):
      attn.flash_attention(q, k, v, 1, causal=causal, window=window)


@pytest.mark.parametrize("t,bq,bk,window", [
    (4096, 512, 512, 512), (4096, 256, 256, 512), (4096, 128, 128, 512),
    (4096, 256, 512, 512), (4096, 512, 256, 512), (256, 64, 32, 23),
    (256, 32, 64, 1), (256, 64, 64, 200)])
def test_loops_visit_the_band_tiles_and_no_other(t, bq, bk, window):
  """Each q block streams exactly the k blocks that hold an entry of the
  band for one of its rows, and each k block's backward loop exactly the q
  blocks whose rows see one of its keys."""
  def in_band(q_lo, q_hi, k_lo, k_hi):
    rows, keys = np.arange(q_lo, q_hi)[:, None], np.arange(k_lo, k_hi)
    return bool(np.any((rows >= keys) & (rows - keys < window)))

  for qb in range(t // bq):
    first, end = attn._k_block_range(qb, bq, bk, t // bk, True, window)
    want = [kb for kb in range(t // bk)
            if in_band(qb * bq, (qb + 1) * bq, kb * bk, (kb + 1) * bk)]
    assert (int(first), int(end)) == (want[0], want[-1] + 1), qb
  for kb in range(t // bk):
    first, end = attn._q_block_range(kb, bk, bq, t // bq, True, window)
    want = [qb for qb in range(t // bq)
            if in_band(qb * bq, (qb + 1) * bq, kb * bk, (kb + 1) * bk)]
    assert (int(first), int(end)) == (want[0], want[-1] + 1), kb


def test_tiles_visited_at_the_cells_shape():
  """At T 4096 and W 512 (the cell's sliding layers), the tiles the forward
  visits over the band's 1,966,336 entries a head: 2.00 x at 512-blocks,
  1.50 x at 256, 1.25 x at 128; 15 of the causal call's 36 tiles at 512,
  45 of 136 at 256."""
  band = 512 * 513 // 2 + (4096 - 512) * 512
  assert band == 1_966_336
  for block, share, tiles, causal_tiles in ((512, 2.0, 15, 36),
                                            (256, 1.5, 45, 136),
                                            (128, 1.25, None, None)):
    visited = sum(
        int(end) - int(first) for first, end in (
            attn._k_block_range(qb, block, block, 4096 // block, True, 512)
            for qb in range(4096 // block)))
    causal = sum(int(attn._k_block_range(qb, block, block, 4096 // block,
                                         True, None)[1])
                 for qb in range(4096 // block))
    assert visited * block * block / band == pytest.approx(share, rel=2e-3)
    if tiles is not None:
      assert (visited, causal) == (tiles, causal_tiles)


# -- no window: the kernels are those of before -------------------------------


def _kernel_bodies(fn, *args):
  """(name, digest) of every pallas_call `fn` traces: its kernel body's
  jaxpr, grid, compiler parameters and results."""
  out = []

  def walk(jaxpr):
    for eqn in jaxpr.eqns:
      if eqn.primitive.name == "pallas_call":
        p = eqn.params
        text = (str(p["jaxpr"]) + str(p["grid_mapping"])
                + str(p["compiler_params"]) + str(p["out_avals"]))
        out.append((p["name"], hashlib.sha256(text.encode()).hexdigest()[:16]))
      for value in eqn.params.values():
        inner = getattr(value, "jaxpr", value)
        if hasattr(inner, "eqns"):
          walk(inner)

  walk(jax.make_jaxpr(fn)(*args).jaxpr)
  return out


# The Mosaic kernels' bodies as the tree before the window read them (the
# same digests, taken there with the same function).
BEFORE = {
    ((1, 256, 2, 128), True, 128, 128): [("flash_fwd", "95261d1f0b32e281"),
                                         ("flash_bwd", "ed062a76c92d53e6")],
    ((1, 256, 2, 128), False, 128, 128): [("flash_fwd", "8566f4435df5f055"),
                                          ("flash_bwd", "76869b4924f1d138")],
    ((2, 300, 4, 64), True, 64, 128): [("flash_fwd", "f9d9fd5d6c41297c"),
                                       ("flash_bwd", "b0d20a9eb1a54913")],
    ((1, 256, 8, 32), False, 128, 64): [("flash_fwd", "3dc787911a8fea89"),
                                        ("flash_bwd", "a9669d42724ba09a")],
}


@pytest.mark.parametrize("case", list(BEFORE), ids=str)
def test_no_window_leaves_the_kernels_as_they_were(case):
  (b, t, h, d), causal, bq, bk = case
  shape = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16)

  def grads(**window):
    return jax.grad(lambda q, k, v: attn.flash_attention(
        q, k, v, h, causal=causal, block_q=bq, block_k=bk, interpret=False,
        **window).astype(jnp.float32).sum(), argnums=(0, 1, 2))

  assert _kernel_bodies(grads(), shape, shape, shape) == BEFORE[case]
  assert _kernel_bodies(grads(window=None), shape, shape,
                        shape) == BEFORE[case]
  if causal:
    windowed = _kernel_bodies(grads(window=t // 4), shape, shape, shape)
    assert [name for name, _ in windowed] == ["flash_window_fwd",
                                              "flash_window_bwd"]


# -- YaRN ---------------------------------------------------------------------

# The catalog's `rope_parameters` of Laguna-XS.2's full-attention layers.
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}


def _published_yarn(head_dim: int, rope: dict):
  """huggingface transformers' `_compute_yarn_parameters`, transcribed in
  numpy: (inv_freq, attention_factor, (low, high))."""
  base = rope["rope_theta"]
  dim = int(head_dim * rope["partial_rotary_factor"])
  factor = rope["factor"]
  original = rope["original_max_position_embeddings"]

  def find_correction_dim(num_rotations):
    return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (
        2 * math.log(base))

  low = max(math.floor(find_correction_dim(rope["beta_fast"])), 0)
  high = min(math.ceil(find_correction_dim(rope["beta_slow"])), dim - 1)
  pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
  extrapolation = 1.0 / pos_freqs
  interpolation = 1.0 / (factor * pos_freqs)
  ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                 / (high - low), 0, 1)
  extrapolation_factor = 1 - ramp
  inv_freq = (interpolation * (1 - extrapolation_factor)
              + extrapolation * extrapolation_factor)
  return inv_freq, rope["attention_factor"], (low, high)


def test_yarn_tables_are_the_published_formula():
  inv_freq, scale, (low, high) = _published_yarn(128, YARN)
  # dim 64: 64 ln(4096 / (64 x 2 pi)) / (2 ln 500000) = 5.66 -> 5, and at
  # beta 1 15.80 -> 16.
  assert (low, high) == (5, 16)
  cos, sin = decoder.yarn_rotary_tables(4096, 128, YARN)
  assert cos.shape == sin.shape == (4096, 32)
  # Position 1's angle is inv_freq itself (each under pi); the tables'
  # length there is the scale.
  np.testing.assert_allclose(np.hypot(cos[1], sin[1]), scale, rtol=1e-6)
  got = np.arctan2(np.asarray(sin[1]), np.asarray(cos[1]))
  np.testing.assert_allclose(got, inv_freq, rtol=2e-5)
  extra = 500000.0 ** (-np.arange(0, 64, 2) / 64)
  for j in (0, 4, 5):                  # below the range: extrapolated
    assert got[j] == pytest.approx(extra[j], rel=2e-5)
  for j in (16, 20, 31):               # above it: interpolated by 64
    assert got[j] == pytest.approx(extra[j] / 64, rel=2e-5)
  ramp = (10 - 5) / (16 - 5)           # inside it: blended
  assert got[10] == pytest.approx(
      extra[10] / 64 * ramp + extra[10] * (1 - ramp), rel=2e-5)
  np.testing.assert_allclose(cos[4095], np.cos(4095 * inv_freq) * scale,
                             atol=5e-4)


def test_laguna_kinds_take_their_own_rotary():
  cfg = decoder.DecoderConfig(
      head_dim=128, rope_parameters=decoder.freeze_rope_parameters({
          "full_attention": YARN,
          "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                "partial_rotary_factor": 1},
          "original_max_position_embeddings": 4096}))
  for got, want in zip(decoder.laguna_rotary_tables(cfg, "sliding", 256),
                       decoder.rotary_tables(256, 128, 10000)):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
  for got, want in zip(decoder.laguna_rotary_tables(cfg, "global", 256),
                       decoder.yarn_rotary_tables(256, 128, YARN)):
    assert got.shape == (256, 32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
