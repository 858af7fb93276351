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

The kernel compiles to Mosaic for the TPU; ``interpret=True`` runs it in
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
