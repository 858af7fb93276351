"""Pallas TPU kernels for the consensus hot path.

The per-round tally that advances ``commitIndex`` — the k-th largest
``matchIndex`` across the peer axis (Raft's quorum median; BASELINE.json's
"quorum-vote tally / commitIndex advance" lift) — is computed here as a
blocked Pallas kernel instead of ``jnp.sort``:

- layout is ``[P, G]`` so the huge group axis rides the 128-wide vector
  lanes and the tiny peer axis (3/5/7) sits in sublanes;
- selection is ``k-1`` rounds of masked max-extraction (P and k are
  static), all in VMEM registers — no general sort network;
- the same closed-form selection is also provided as a pure-jnp reference
  (``kth_largest``), the default path and the differential-test oracle.

The committed window of phase 5 — A contiguous log indexes, so a cyclic run
of each replica's L-slot ring — is read here too (``ring_window_pallas``):
a rotation of the ring by the run's first slot, as a barrel shifter of
``ceil(log2(L))`` stages over ``[P, L, G]`` blocks (groups on the lanes,
ring slots on the sublanes, which is how the log planes lie in HBM
already), each stage one ``pltpu.roll`` along the sublanes and one select.
It exists as a kernel only: spelled in jnp, a stage's rotation is static
slices, which the TPU compiler turns into relayout copies where the shift
is no multiple of the 8-slot sublane tile (six planes on a v5e: 6.1 ms,
13.2 ms with ``jnp.roll``, against 1.37 ms for the ``[A, L]`` one-hot
select-reduce and 0.95 ms for the kernel; PERF.md §6, PR 52), so where the
kernel is off ``ops.consensus`` keeps the one-hot.

The kernels compile to Mosaic for the TPU; ``interpret=True`` runs them in
Pallas's interpreter (tests on the CPU). The caller says which — gate via
``Config.use_pallas`` / ``Config.pallas_interpret`` (``ops.consensus``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

INT_MIN = jnp.iinfo(jnp.int32).min


def kth_largest(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """k-th largest along axis 1 of ``x [G, P]`` (k is 1-based), in jnp.

    Masked max-extraction — O(k·P) elementwise ops, no sort. The oracle
    for the Pallas kernel and the default consensus path.
    """
    m = x
    for _ in range(k - 1):
        mx = jnp.max(m, axis=1, keepdims=True)
        is_mx = m == mx
        first = (jnp.cumsum(is_mx.astype(jnp.int32), axis=1) == 1) & is_mx
        m = jnp.where(first, INT_MIN, m)
    return jnp.max(m, axis=1)


def kth_largest_masked(x: jnp.ndarray, mask: jnp.ndarray,
                       k: jnp.ndarray) -> jnp.ndarray:
    """k-th largest of ``x [G, P]`` among ``mask [G, P]`` lanes, with a
    PER-GROUP dynamic ``k [G]`` (1-based).

    The dynamic-membership quorum tally: masked-out (non-member) lanes are
    excluded, and k varies per group (``count//2 + 1`` of each group's
    member count). Static-k masked max-extraction can't express a traced
    k, so this uses the same O(P²) pairwise rank-select as the Pallas
    kernel — each element's tie-broken descending rank is unique, and
    exactly one element matches rank k-1 (provided k ≤ member count,
    which quorum-of-members guarantees).
    """
    P = x.shape[1]
    xm = jnp.where(mask, x, INT_MIN)
    r_val = xm[:, :, None]                    # element r   [G,P,1]
    s_val = xm[:, None, :]                    # vs s        [G,1,P]
    r_idx = jnp.arange(P, dtype=jnp.int32)[None, :, None]
    s_idx = jnp.arange(P, dtype=jnp.int32)[None, None, :]
    beats = (s_val > r_val) | ((s_val == r_val) & (s_idx < r_idx))
    rank = jnp.sum(beats.astype(jnp.int32), axis=2)          # [G,P]
    sel = rank == (k - 1)[:, None]
    return jnp.sum(jnp.where(sel, xm, 0), axis=1)


def _kth_kernel(x_ref, out_ref, *, k: int):
    """Block kernel: x [P, BG] -> out [1, BG] (k-th largest over axis 0).

    Rank-select instead of sort or masked max-extraction: Mosaic has no
    cumsum lowering, so each row's tie-broken descending rank is computed
    with O(P²) pairwise compares (P is 3-7) and exactly one row matches
    rank k-1.
    """
    m = x_ref[...]
    P = m.shape[0]
    r_val = m[:, None, :]                     # row r        [P,1,BG]
    s_val = m[None, :, :]                     # vs row s     [1,P,BG]
    r_idx = jax.lax.broadcasted_iota(jnp.int32, (P, P, 1), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (P, P, 1), 1)
    beats = (s_val > r_val) | ((s_val == r_val) & (s_idx < r_idx))
    rank = jnp.sum(beats.astype(jnp.int32), axis=1)  # [P,BG]
    sel = rank == (k - 1)
    out_ref[...] = jnp.sum(jnp.where(sel, m, 0), axis=0, keepdims=True)


def _kth_blocks(x: jnp.ndarray, k: int, block: int,
                interpret: bool) -> jnp.ndarray:
    from jax.experimental import pallas as pl

    G, P = x.shape
    Gp = (G + block - 1) // block * block
    xt = jnp.transpose(x)  # [P, G] — groups on the lane axis in the kernel
    if Gp != G:
        xt = jnp.pad(xt, ((0, 0), (0, Gp - G)), constant_values=INT_MIN)

    out = pl.pallas_call(
        functools.partial(_kth_kernel, k=k),
        grid=(Gp // block,),
        in_specs=[pl.BlockSpec((P, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Gp), x.dtype),
        interpret=interpret,
    )(xt)
    return out[0, :G]


@functools.partial(jax.jit,
                   static_argnames=("k", "block", "interpret", "mesh"))
def kth_largest_pallas(x: jnp.ndarray, k: int, block: int = 512,
                       interpret: bool = False, mesh=None) -> jnp.ndarray:
    """k-th largest along axis 1 of ``x [G, P]`` via a Pallas TPU kernel.

    ``mesh``: the mesh ``x`` is sharded over, if any. A Mosaic kernel
    cannot be partitioned automatically (the TPU compiler refuses a
    sharded operand outright), so under a mesh the kernel runs inside a
    ``shard_map``: groups are independent, so each device tallies its own
    block of the group axis — no collective — and the peer axis, which
    the tally reduces over, is gathered whole first when the mesh shards
    it."""
    kernel = functools.partial(_kth_blocks, k=k, block=block,
                               interpret=interpret)
    if mesh is None:
        return kernel(x)
    g = "groups" if "groups" in mesh.axis_names else None
    return jax.shard_map(kernel, mesh=mesh, in_specs=P(g, None),
                         out_specs=P(g), check_vma=False)(x)


def _ring_shifts(L: int) -> list[int]:
    return [1 << b for b in range((L - 1).bit_length())]


def ring_window_fits(A: int, L: int) -> bool:
    """Whether :func:`ring_window_pallas` takes a window of A from a ring
    of L: whole sublane tiles in and out, the window no longer than the
    ring."""
    return A <= L and A % 8 == 0 and L % 8 == 0


def _ring_kernel(s_ref, log_ref, out_ref, *, A: int):
    """Block kernel: s [P, BG], log [P, L, BG] -> out [P, A, BG]."""
    from jax.experimental.pallas import tpu as pltpu

    peers, L, _ = log_ref.shape
    for p in range(peers):
        x = log_ref[p]
        s = s_ref[p:p + 1, :]
        for k in _ring_shifts(L):
            x = jnp.where((s & k) != 0, pltpu.roll(x, L - k, 0), x)
        out_ref[p] = x[:A]


def _ring_blocks(log: jnp.ndarray, s0: jnp.ndarray, A: int, block: int,
                 interpret: bool) -> jnp.ndarray:
    from jax.experimental import pallas as pl

    G, P, L = log.shape
    block = min(block, (G + 127) // 128 * 128)
    # [P, L, G] and [P, G]: the planes' own order in HBM (groups
    # minor-most, then slots, then peers), so no copy is made of them.
    # The fence keeps the transpose out of the fusion that wrote the
    # plane: fused, the TPU compiler computes the plane twice, once in
    # each logical shape (the raw scan's one pass over the log became
    # three fusions, compiled for a described v5e, PR 52).
    out = pl.pallas_call(
        functools.partial(_ring_kernel, A=A),
        grid=(pl.cdiv(G, block),),
        in_specs=[pl.BlockSpec((P, block), lambda g: (0, g)),
                  pl.BlockSpec((P, L, block), lambda g: (0, 0, g))],
        out_specs=pl.BlockSpec((P, A, block), lambda g: (0, 0, g)),
        out_shape=jax.ShapeDtypeStruct((P, A, G), log.dtype),
        interpret=interpret,
    )(jnp.transpose(s0), jnp.transpose(jax.lax.optimization_barrier(log),
                                       (1, 2, 0)))
    from jax.experimental.layout import Layout, with_layout_constraint
    return with_layout_constraint(jnp.transpose(out, (2, 0, 1)),
                                  Layout(major_to_minor=(1, 2, 0)))


@functools.partial(jax.jit,
                   static_argnames=("A", "block", "interpret", "mesh"))
def ring_window_pallas(log: jnp.ndarray, s0: jnp.ndarray, A: int,
                       block: int = 1024, interpret: bool = False,
                       mesh=None) -> jnp.ndarray:
    """``log [G, P, L]`` to ``[G, P, A]``: the ring's slots ``(s0 + i) % L``
    for ``i < A`` (``s0 [G, P]`` in ``[0, L)``), whatever they hold, via a
    Pallas TPU kernel; ``A <= L``, both multiples of the 8-slot sublane
    tile (:func:`ring_window_fits`). Under a ``mesh`` it runs
    inside a ``shard_map`` over the group axis, as
    :func:`kth_largest_pallas` does and for the same reason; a peer axis
    the mesh shards is gathered whole first."""
    kernel = functools.partial(_ring_blocks, A=A, block=block,
                               interpret=interpret)
    if mesh is None:
        return kernel(log, s0)
    g = "groups" if "groups" in mesh.axis_names else None
    return jax.shard_map(kernel, mesh=mesh,
                         in_specs=(P(g, None, None), P(g, None)),
                         out_specs=P(g, None, None),
                         check_vma=False)(log, s0)
