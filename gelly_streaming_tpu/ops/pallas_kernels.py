"""Pallas TPU kernels for the dense model path.

Where Pallas pays off here is the MXU-dense side of the framework: the
GraphSAGE layer computes ``act(h @ W_self + agg @ W_nbr + b)`` — two
matmuls whose [V, O] intermediates XLA materializes between fusions.
:func:`fused_sage_matmul` keeps one [TILE_V, TILE_O] accumulator in VMEM
across both contractions, writing each output tile once. Round-2
re-measurement on the chip ([65536, 256] x [256, 256] x 2, bf16):
0.024 ms fused vs 0.031 ms XLA dual-matmul — kept, opt-in.

The scatter/gather graph kernels (segment reductions, label propagation,
row intersection) deliberately stay on XLA. The two queued round-1
candidates were evaluated with measurements (round-2):

- **Sorted-run segmented reduction** — REJECTED. TPU Pallas has no
  arbitrary vector scatter, so the only hand-written shape is the
  scatter-free formulation (cumsum + run-boundary gather over pre-sorted
  keys). Measured on the chip at [1M edges -> 262k segments]:
  XLA scatter-add 12.7 ms vs cumsum+gather 93.7 ms — the f32 prefix scan
  over 1M elements costs far more than the scatter it removes. The XLA
  scatter path stays.
- **Double-buffered HBM->VMEM membership pass** (triangle row
  intersection) — REJECTED as not load-bearing: the XLA membership kernel
  already measures 10.5e9 edges/s at the 1M-edge window bench (BENCH
  detail), three orders of magnitude above the host-bound end-to-end
  rate; streaming row pairs by hand cannot move any system number.

Off-TPU the kernel runs only in ``interpret=True`` mode, which the caller
passes explicitly (the CPU test suite does); asking for the compiled
kernel where it cannot run raises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _pad_to(x: jax.Array, mult0: int, mult1: int) -> jax.Array:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(
    jax.jit, static_argnames=("activation", "tile_v", "tile_o", "interpret")
)
def fused_sage_matmul(
    h: jax.Array,
    agg: jax.Array,
    w_self: jax.Array,
    w_nbr: jax.Array,
    b: jax.Array,
    activation: str = "relu",
    tile_v: int = 256,
    tile_o: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """``act(h @ w_self + agg @ w_nbr + b)`` as one Pallas kernel.

    ``h``/``agg``: [V, F]; weights [F, O]; bias [O]. Accumulation is f32
    regardless of input dtype (bf16 in, f32 accumulate, input-dtype out —
    the MXU-native recipe). Returns [V, O] in ``h.dtype``.
    """
    from jax.experimental import pallas as pl

    if activation not in ("relu", "none"):
        raise ValueError(
            f"fused_sage_matmul supports activation 'relu' or 'none', "
            f"got {activation!r}"
        )
    V, F = h.shape
    o_dim = w_self.shape[1]
    dtype = h.dtype
    hp = _pad_to(h, tile_v, 128)
    ap = _pad_to(agg, tile_v, 128)
    wsp = _pad_to(w_self, 128, tile_o)
    wnp = _pad_to(w_nbr, 128, tile_o)
    bp = jnp.pad(b, (0, wsp.shape[1] - o_dim))[None, :]
    Vp, Fp = hp.shape
    Op = wsp.shape[1]

    def kernel(h_ref, a_ref, ws_ref, wn_ref, b_ref, out_ref):
        acc = jnp.dot(
            h_ref[:], ws_ref[:], preferred_element_type=jnp.float32
        )
        acc += jnp.dot(
            a_ref[:], wn_ref[:], preferred_element_type=jnp.float32
        )
        acc += b_ref[:].astype(jnp.float32)
        if activation == "relu":
            acc = jnp.maximum(acc, 0.0)
        out_ref[:] = acc.astype(out_ref.dtype)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((Vp, Op), dtype),
        grid=(Vp // tile_v, Op // tile_o),
        in_specs=[
            pl.BlockSpec((tile_v, Fp), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_v, Fp), lambda i, j: (i, 0)),
            pl.BlockSpec((Fp, tile_o), lambda i, j: (0, j)),
            pl.BlockSpec((Fp, tile_o), lambda i, j: (0, j)),
            pl.BlockSpec((1, tile_o), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_v, tile_o), lambda i, j: (i, j)),
        interpret=interpret,
    )(hp, ap, wsp, wnp, bp)
    return out[:V, :o_dim]


def require_tpu_for_pallas() -> None:
    """Raise unless the default device is a TPU: the compiled kernel runs
    nowhere else. Asking for it off-TPU is an error, not a silent detour
    through XLA; a test that wants the interpreter passes
    ``interpret=True`` to :func:`fused_sage_matmul` itself."""
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"the Pallas kernel was asked for on platform {platform!r}; it "
            "compiles only for a TPU (tests pass interpret=True to "
            "fused_sage_matmul directly)"
        )
