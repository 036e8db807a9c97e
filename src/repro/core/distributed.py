"""SPMD schedules for the distributed ``AᵀA`` product (paper §4.2 / §4.3).

The paper's parallel insight: schedule the symmetric product as **disjoint,
α-balanced tasks** over the lower triangle of C (threads/ranks never collide
on writes), and **retrieve only packed lower-triangular payloads**. Its
transport — MPI scatter/gather trees from a root rank — would serialize on
one chip on a TPU pod, so the schedules here map the same insight onto
jax-native SPMD (see DESIGN.md §2):

* :func:`gram_rowshard` — A row-sharded (the ``C = Σ_p A_pᵀA_p`` view, i.e.
  the C11 recursion collapsed onto the mesh): local ATA + one ``psum``.
  This is the pure-DP gram used by the Shampoo optimizer for row-sharded
  gradients. With ``out='packed'`` the psum payload is the packed
  ``SymmetricMatrix`` block stack — ``T·bn² ≈ n²/2`` words per reduce
  instead of the dense ``n²`` (the collective-bytes halving the packed
  optimizer statistics ride on).

* :func:`ata_tile_parallel` — the ATA-S/ATA-D analogue. C's lower triangle
  is tiled into ``nb(nb+1)/2`` uniform ``w×w`` tiles, assigned contiguously
  to the devices of ``task_axis`` (uniform shapes keep the program SPMD);
  each device computes its tiles with the sequential ATA/Strassen machinery
  at the leaf level (paper §4.1.3: "Strassen can still be used at
  leaf-level computation") — including the level-synchronous
  ``leaf_dispatch='batched'`` formulation when the plan picks it, so each
  device's tile products cost O(levels) dispatched ops, not O(7^L)
  (DESIGN.md §4), and the fused-operand ``'fused'`` dispatch, whose ±1
  leaf combinations never materialize an operand stack in any per-device
  body (DESIGN.md §2). Partial sums over a ``row_axis`` (if A is also
  row-sharded — the ATA-D two-level layout) are combined with a single
  ``psum`` **of the packed tile stack** — ``T·w² ≈ n²/2`` words instead of
  the dense ``n²``, reproducing the paper's packed-low(C) retrieval saving
  (Prop. 4.2) as a collective-bytes saving. Retrieval keeps that form:
  ``out='packed'`` assembles a :class:`~repro.core.symmetric
  .SymmetricMatrix` straight from the tile stack (a pure slice when the
  stripe width matches the packed block grid — no dense buffer anywhere),
  and the dense mode is just its ``to_dense()`` at the root — the mirrored
  replicated square the seed materialized unconditionally is now opt-in.

* :func:`gemm_tn_colshard` — the distributed FastStrassen companion:
  ``C = AᵀB`` with B column-sharded; each device owns a disjoint column
  stripe of C (no collision, no reduction).

Correspondence with ``repro.core.task_tree``: the task tree is the faithful
scheduler model (heterogeneous leaf shapes — fine for MPI ranks, hostile to
SPMD). The block-cyclic tiling here is the shape-uniform realization of the
same disjoint-task principle; `tests/test_distributed.py` checks that both
cover the lower triangle exactly once and that flop balance matches the
LPT model within the tile-granularity bound.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro import obs
from repro.core.ata import ata
from repro.core.precision import dot_precision
from repro.core.strassen import _plan_base_fns, strassen_tn, tree_depth
from repro.core.symmetric import SymmetricMatrix, sym_tile

__all__ = [
    "gram_rowshard",
    "ata_tile_parallel",
    "ata_bfs_dfs",
    "bfs_dfs_assignment",
    "gemm_tn_colshard",
    "choose_tiling",
    "tile_parallel_device_flops",
]


def _collective(name: str, fn, x, **attrs):
    """``fn(x)``, one collective of a schedule, under its ``distributed.*``
    scope (``distributed.psum`` or ``distributed.psum_scatter``), with the
    payload each device hands it (every leaf of ``x``) added at trace time
    to the ``distributed.collective_bytes`` counter."""
    obs.metrics.inc("distributed.collective_bytes",
                    sum(leaf.size * leaf.dtype.itemsize
                        for leaf in jax.tree.leaves(x)))
    with obs.span(name, **attrs):
        return fn(x)


# ---------------------------------------------------------------------------
# rowshard: C = Σ_p A_pᵀ A_p
# ---------------------------------------------------------------------------


def gram_rowshard(
    a_local: jax.Array,
    axis: str,
    *,
    plan=None,
    n_base: Optional[int] = None,
    variant: Optional[str] = None,
    leaf_dispatch: Optional[str] = None,
    use_ata: Optional[bool] = None,
    out: str = "dense",
    packed_block: Optional[int] = None,
) -> Union[jax.Array, SymmetricMatrix]:
    """Per-device gram + all-reduce. Call **inside** shard_map/pjit-manual.

    ``a_local`` is this device's row block; the result is the full replicated
    ``AᵀA``. The local product uses the sequential ATA algorithm, so the
    paper's 2/3-Strassen flop saving applies on every chip. Tunables resolve
    through the planner (`repro.tune.plan` on the local shape) unless pinned
    — including ``leaf_dispatch``: the per-device body reuses the batched
    or fused leaf formulation when the plan (or the caller) asks for it, so
    the SPMD schedule inherits the O(levels)-jaxpr win per shard (and, for
    ``'fused'``, the zero-operand-stack leaf combine). ``use_ata=False``
    — or a plan whose algorithm is ``'dense'`` — falls back to the
    classical one-dot gram.

    ``out='packed'`` keeps the paper's low(C) form **across the psum**: the
    local gram comes out of ``ata(..., out='packed')`` mirror-free and the
    all-reduce moves the packed ``(T, bn, bn)`` block stack — ``≈ n²/2``
    words instead of the dense ``n²`` — returning a replicated
    :class:`SymmetricMatrix`. (``SymmetricMatrix`` is a pytree, so the
    caller's ``shard_map`` needs a 3-axis out_spec, e.g. ``P(None, None,
    None)``.)
    """
    if out not in ("dense", "packed"):
        raise ValueError(f"unknown output mode {out!r}; use 'dense' or 'packed'")
    if use_ata is None:
        use_ata = plan is None or plan.algorithm != "dense"
    obs.metrics.inc("dispatch.gram_rowshard")
    with obs.span("distributed.gram_rowshard", out=out, use_ata=use_ata):
        if use_ata:
            local = ata(
                a_local, plan=plan, n_base=n_base, variant=variant,
                leaf_dispatch=leaf_dispatch, out=out, packed_block=packed_block,
            )
        else:
            local = jax.lax.dot_general(
                a_local, a_local, (((0,), (0,)), ((), ())),
                precision=dot_precision(a_local),
                preferred_element_type=jnp.float32,
            )
            if out == "packed":
                if packed_block is None:
                    from repro.tune.defaults import DEFAULT_PACKED_BLOCK

                    packed_block = (
                        plan.packed_block if plan is not None else DEFAULT_PACKED_BLOCK
                    )
                local = SymmetricMatrix.from_dense(local, packed_block)
        # psum maps over the SymmetricMatrix pytree leaf — the packed stack
        # is the collective payload, never a mirrored square.
        return _collective("distributed.psum",
                           lambda x: jax.lax.psum(x, axis), local,
                           axis=axis, out=out)


# ---------------------------------------------------------------------------
# tile-parallel: block-cyclic lower-triangle tiles over a mesh axis
# ---------------------------------------------------------------------------


def choose_tiling(
    n: int,
    p: int,
    target_tiles_per_dev: Optional[int] = None,
    *,
    out: str = "dense",
    packed_block: Optional[int] = None,
) -> tuple[int, int]:
    """Pick (nb, w): nb stripe count, w stripe width (multiple of 8).

    Delegates to the planner's distributed branch
    (`repro.tune.cost.distributed_tiling`) — kept as the public name the
    SPMD schedules and tests use. ``out='packed'`` lets the search snap the
    stripe width to the packed block grid (pure-slice retrieval).
    """
    from repro.tune.cost import distributed_tiling

    return distributed_tiling(
        n, p, target_tiles_per_dev, out=out, packed_block=packed_block
    )


def _auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which the
    schedules' root-level re-tiling of a sharded tile stack (slices and
    scatters across the sharded dim) is a type error. The schedules leave
    that data movement to the compiler, as on an ``Auto`` mesh.
    """
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _tri_coords_traced(t):
    tf = t.astype(jnp.float32)
    i = jnp.floor((jnp.sqrt(8.0 * tf + 1.0) - 1.0) / 2.0).astype(jnp.int32)
    i = jnp.where((i + 1) * (i + 2) // 2 <= t, i + 1, i)
    i = jnp.where(i * (i + 1) // 2 > t, i - 1, i)
    j = t - i * (i + 1) // 2
    return i, j


def _tile_fn(w, *, plan, use_strassen, n_base, variant, leaf_dispatch,
             acc_dtype):
    """``compute_tile(a_local, t)``: tri-order tile ``t`` of ``a_localᵀa_local``
    over stripes of width ``w`` — the sliced tile body of :func:`_tile_stack`.
    It cuts the tile's two column stripes out of ``a_local`` and hands them
    to ``strassen_tn``; a plan with ``use_kernels`` puts its Pallas TN kernel
    at the bottom of every tile's Strassen recursion. Used where a tile is
    not one kernel leaf (``w > n_base``: a recursion inside the tile), by
    plans without kernels or Strassen, and where the stripe-major copy
    would not fit the device (:func:`_takes_direct`); otherwise the direct
    path of :func:`_direct_stack` computes the whole stack without the
    cuts."""
    _, base_dot = _plan_base_fns(plan, None, None)

    def compute_tile(a_local, t):
        i, j = _tri_coords_traced(t)
        ai = jax.lax.dynamic_slice_in_dim(a_local, i * w, w, axis=1)
        aj = jax.lax.dynamic_slice_in_dim(a_local, j * w, w, axis=1)
        if use_strassen:
            return strassen_tn(
                ai, aj, n_base=n_base, variant=variant,
                leaf_dispatch=leaf_dispatch, base_dot=base_dot,
                acc_dtype=acc_dtype,
            )
        return jax.lax.dot_general(
            ai, aj, (((0,), (0,)), ((), ())),
            precision=dot_precision(ai, aj),
            preferred_element_type=acc_dtype,
        )

    return compute_tile


def _slot_tile(compute_tile, a_local, g, valid, like):
    """One per-device tile slot: tile ``g``, or a zero dummy tile.

    When T % p ≠ 0 some devices own dummy slots. They are **masked to a
    zero tile** behind ``lax.cond`` — real control flow, so the dummy's
    dot never runs — which keeps the exact LPT flop model
    (:func:`tile_parallel_device_flops`, regression-tested). ``valid`` is
    ``True`` for a slot that is real on every device, which skips the cond
    statically; otherwise it is the traced predicate, and ``g`` must
    already be clamped to a real tile id.

    Under ``shard_map`` the computed tile varies over the mesh axes its
    inputs vary over (``g`` comes from ``axis_index``), so the zero tile
    is cast to the same varying axes: ``cond`` requires both branches to
    have one type, varying manual axes included. Its shape and dtype come
    from ``like``, the ``eval_shape`` of a real tile, so they follow
    ``acc_dtype``.
    """
    if valid is True:
        return compute_tile(a_local, g)
    vma = tuple(sorted(jax.typeof(a_local).vma | jax.typeof(g).vma))

    def dummy():
        z = jnp.zeros(like.shape, like.dtype)
        return jax.lax.pcast(z, vma, to="varying") if vma else z

    return jax.lax.cond(valid, lambda: compute_tile(a_local, g), dummy)


def _direct_stack(a_local, ids, w, plan, acc_dtype):
    """The tile stack as ONE ``gemm_tn_fused`` launch over a stripe-major
    copy of ``a_local``.

    ``a_local`` ``(m_l, nb·w)`` is relaid once, under the
    ``distributed.stripes`` scope, into the block-major leaf grid
    ``(1, 1, nb, m_l, w)`` (``core.strassen._to_blocks``'s layout with one
    block row and ``nb`` block columns): ``m_l·nb·w`` words more on the
    device, in place of two ``(m_l, w)`` stripe copies per tile. Slot ``q``
    is a one-slot leaf reading stripe ``i`` as A and stripe ``j`` as B, sign
    1, or sign 0 for a dummy slot (``ids[q] < 0``) — a dead leaf, whose dot
    the kernel skips. The kernel's chunking is ``gemm_tn_pallas``'s on the
    cut stripes and a ×1 sign is exact, so each tile is bitwise the sliced
    path's.
    """
    from repro.kernels import ops

    m_l, n_pad = a_local.shape
    with obs.span("distributed.stripes", nb=n_pad // w, w=w):
        # a stack of static slices: XLA writes it in one pass, in place,
        # where a reshape + moveaxis compiles for the TPU to two full copies
        # (through a column-major A) and twice the memory
        stripes = jnp.stack([a_local[:, k * w:(k + 1) * w]
                             for k in range(n_pad // w)])[None, None]
    i, j = _tri_coords_traced(jnp.maximum(ids, 0))
    rows = jnp.zeros_like(i)[:, None]
    sgn = (ids >= 0).astype(jnp.int32)[:, None]
    tables = ((rows, i[:, None], sgn), (rows, j[:, None], sgn))
    return ops.gemm_tn_fused(stripes, stripes, tables, plan=plan,
                             out_dtype=acc_dtype)


def _takes_direct(plan, use_strassen, n_base, *, m, nb, w, schedule, p_task,
                  d_row, out, itemsize):
    """Whether the tile stack takes the direct path (:func:`_direct_stack`).

    It does where the plan runs the Pallas kernels, a tile is one leaf of
    ``strassen_tn`` (no recursion inside it: ``min(m_local, w) ≤ n_base``),
    and the planner's per-device memory figure with the stripe-major copy
    of the operand slab counted (``tune.cost.comm_memory_bytes(…,
    stripes=True)``) fits the plan's machine — the planner admits a
    schedule by the figure without the copy, so a plan that fits only
    without it keeps the sliced path.
    """
    from repro.tune.cost import comm_memory_bytes, machine_for

    if not (plan is not None and plan.use_kernels and use_strassen
            and tree_depth((m // d_row, w, w), n_base) == 0):
        return False
    need = comm_memory_bytes(schedule, nb, w, p_task, d_row, m=m, out=out,
                             itemsize=itemsize, stripes=True)
    return need <= machine_for(plan.backend).device_memory_bytes


def _tile_stack(a_local, ids, always_live, w, *, direct, plan, use_strassen,
                n_base, variant, leaf_dispatch, acc_dtype):
    """This device's ``(s, w, w)`` stack of tile slots, under the
    ``distributed.tile_body`` scope — the body both tile schedules share.

    ``ids`` is the traced ``(s,)`` int32 vector of the device's tri-order
    tile ids, ``-1`` for a dummy slot; ``always_live[q]`` is ``True`` where
    slot ``q`` is real on every device. A dummy slot's product never runs
    and its tile is zeros.

    ``direct`` (from :func:`_takes_direct`, which reads only the plan and
    the shapes) makes the whole stack one fused launch
    (:func:`_direct_stack`, counted by ``distributed.tiles_direct``).
    Otherwise each slot cuts its stripes and runs ``strassen_tn``
    (:func:`_tile_fn`, counted by ``distributed.tiles_sliced``): the
    python-unrolled slot loop keeps every tile's products visible to XLA's
    cost model (``lax.map`` would count the body once) and lets XLA
    schedule tiles independently.
    """
    s = len(always_live)
    with obs.span("distributed.tile_body", t_per=s, w=w, direct=direct):
        if direct:
            obs.metrics.inc("distributed.tiles_direct", s)
            return _direct_stack(a_local, ids, w, plan, acc_dtype)
        obs.metrics.inc("distributed.tiles_sliced", s)
        compute_tile = _tile_fn(
            w, plan=plan, use_strassen=use_strassen, n_base=n_base,
            variant=variant, leaf_dispatch=leaf_dispatch, acc_dtype=acc_dtype,
        )
        like = jax.eval_shape(compute_tile, a_local, ids[0])
        return jnp.stack([
            _slot_tile(compute_tile, a_local, ids[q], True, like)
            if always_live[q] else
            _slot_tile(compute_tile, a_local, jnp.maximum(ids[q], 0),
                       ids[q] >= 0, like)
            for q in range(s)
        ])


def _assemble(tiles, n, nb, packed_block, alpha, out, *,
              presymmetrized=False):
    """The schedule's answer from its tri-order tile stack, under the
    ``distributed.assemble`` scope: what the compiler moves between chips
    to assemble it (the psum schedule's diagonal-tile gather) is then
    named in the compiled program like the schedule's own collectives."""
    with obs.span("distributed.assemble", out=out):
        sym = SymmetricMatrix.from_tile_stack(
            tiles, n, nb=nb, packed_block=packed_block,
            presymmetrized=presymmetrized)
        if alpha != 1.0:
            sym = sym.scale(alpha)
        return sym if out == "packed" else sym.to_dense()


def ata_tile_parallel(
    a: jax.Array,
    mesh: Mesh,
    *,
    task_axis: str = "model",
    row_axis: Optional[str] = None,
    alpha: float = 1.0,
    plan=None,
    n_base: Optional[int] = None,
    variant: Optional[str] = None,
    leaf_dispatch: Optional[str] = None,
    use_strassen: bool = True,
    nb: Optional[int] = None,
    out: str = "dense",
    packed_block: Optional[int] = None,
    acc_dtype=jnp.float32,
) -> Union[jax.Array, SymmetricMatrix]:
    """Distributed ``C = alpha·AᵀA`` with disjoint lower-triangle tile tasks.

    Args:
      a: global ``(m, n)``. Sharded ``P(row_axis, None)`` if ``row_axis``
        is given (the row_axis size must divide m), replicated otherwise.
      mesh: the device mesh.
      task_axis: mesh axis that owns disjoint C tiles (the "thread pool" of
        ATA-S / the worker ranks of ATA-D).
      row_axis: optional mesh axis across which the contraction dimension is
        sharded (ATA-D's two-level layout). Partial tiles are psum'ed as a
        packed stack (≈ n²/2 words — the paper's low(C) retrieval saving).
      alpha: scalar applied to the result — in **both** output modes
        (``out='packed'`` scales the packed blocks; the equivalence
        ``alpha·packed == pack(alpha·dense)`` holds bitwise).
      plan: :class:`repro.tune.Plan` (its ``nb``/``tile_w`` distributed
        branch supplies the stripe tiling; ``n_base``/``variant``/
        ``leaf_dispatch`` feed the leaf-level Strassen of every per-device
        tile body — a batched plan runs each device's tile products through
        the level-synchronous one-dot-per-tile dispatch, a fused plan
        through the coefficient-table combine with no operand stacks; a
        kernel plan whose tiles are one leaf computes a device's whole
        stack in one fused launch, see :func:`ata_bfs_dfs`). Default: the
        planner front door with ``devices=p_task`` and the requested
        ``out`` — packed plans snap ``tile_w`` to the packed block grid so
        retrieval is a pure slice.
      leaf_dispatch: explicit override of the plan's leaf dispatch for the
        per-device Strassen bodies (``'unrolled'``/``'batched'``/``'fused'``
        — values are bitwise-identical in every case; ``'fused'`` requires
        the classical variant, so pin ``variant='strassen'`` alongside it
        if the resolved plan picked winograd).
      nb: stripe count override (default: the plan / :func:`choose_tiling`).
      out: ``'dense'`` → replicated ``(n, n)`` array, assembled as
        ``packed.to_dense()`` at the root (one mirror, at the conversion
        boundary). ``'packed'`` → :class:`SymmetricMatrix` built directly
        from the psum'd tile stack: no dense ``(n, n)`` buffer, no mirror,
        no per-tile update loop anywhere in the graph.
      packed_block: packed output grid block size (default: the plan's, or
        ``tune.defaults.DEFAULT_PACKED_BLOCK``); clamped per
        ``symmetric.default_block_size`` for cross-producer compatibility.
      acc_dtype: accumulation dtype of the leaf products (the dummy-slot
        zero tiles follow it — derived via ``eval_shape``, never hardcoded).

    Returns:
      Full symmetric ``(n, n)`` C replicated over the mesh, or its packed
      ``SymmetricMatrix`` form.
    """
    if out not in ("dense", "packed"):
        raise ValueError(f"unknown output mode {out!r}; use 'dense' or 'packed'")
    m, n = a.shape
    p_task = mesh.shape[task_axis]
    if row_axis is not None:
        p_row = mesh.shape[row_axis]
        if m % p_row:
            raise ValueError(
                f"row_axis {row_axis!r} size {p_row} must divide m={m} "
                f"(A is row-sharded P({row_axis!r}, None))"
            )
    if plan is None and n_base is None and variant is None and nb is None:
        from repro.tune import plan as _plan_fn

        plan = _plan_fn(
            op="ata", m=m, n=n, dtype=str(a.dtype), devices=p_task, out=out
        )
    w = None
    if plan is not None:
        n_base = plan.n_base if n_base is None else n_base
        variant = plan.variant if variant is None else variant
        if leaf_dispatch is None:
            leaf_dispatch = getattr(plan, "leaf_dispatch", None)
        if packed_block is None:
            packed_block = plan.packed_block
        if plan.algorithm == "dense":
            use_strassen = False
        # adopt the plan's stripe tiling only if it was built for THIS
        # problem — a plan for another width would tile (and silently
        # truncate) the wrong column range.
        if nb is None and plan.devices == p_task and plan.n == n and plan.nb:
            nb, w = plan.nb, plan.tile_w
    if nb is None:
        nb, w = choose_tiling(n, p_task, out=out, packed_block=packed_block)
    elif w is None:
        w = -(-n // nb)
        w = -(-w // 8) * 8
    n_pad = nb * w
    t_total = nb * (nb + 1) // 2
    t_per = -(-t_total // p_task)

    if n_pad > n:
        a = jnp.pad(a, ((0, 0), (0, n_pad - n)))

    obs.metrics.inc("dispatch.ata_tile_parallel")
    obs.metrics.inc("ata_tile_parallel.tiles", t_total)
    # slot q of device p holds tile p·t_per+q; it is real on every device
    # when the last device's is
    always_live = [(p_task - 1) * t_per + q < t_total for q in range(t_per)]
    direct = _takes_direct(
        plan, use_strassen, n_base, m=m, nb=nb, w=w, schedule=None,
        p_task=p_task, d_row=mesh.shape[row_axis] if row_axis else 1,
        out=out, itemsize=a.dtype.itemsize,
    )

    def local_fn(a_local):
        g = jax.lax.axis_index(task_axis) * t_per + jnp.arange(
            t_per, dtype=jnp.int32)
        tiles = _tile_stack(
            a_local, jnp.where(g < t_total, g, -1), always_live, w,
            direct=direct, plan=plan, use_strassen=use_strassen, n_base=n_base,
            variant=variant, leaf_dispatch=leaf_dispatch, acc_dtype=acc_dtype,
        )
        if row_axis is not None:
            # packed retrieval: reduce the tile stack, not a dense (n, n)
            tiles = _collective("distributed.psum",
                                lambda x: jax.lax.psum(x, row_axis), tiles,
                                axis=row_axis, out="packed")
        return tiles

    in_spec = P(row_axis, None) if row_axis else P(None, None)
    with obs.span("distributed.ata", schedule="psum", tiles=t_total):
        tiles = jax.shard_map(
            local_fn, mesh=_auto_axes(mesh), in_specs=(in_spec,),
            out_specs=P(task_axis, None, None),
        )(a)
        # tiles: global (p_task * t_per, w, w), tri-enumerated — exactly the
        # packed retrieval payload. Assemble the SymmetricMatrix straight
        # from it (pure slice when w matches the packed grid; static re-tile
        # otherwise); dense output is its one root-level mirror.
        return _assemble(tiles, n, nb, packed_block, alpha, out)


# ---------------------------------------------------------------------------
# CAPS-style BFS/DFS schedule (paper §5 / Prop. 4.2 × CAPS, arxiv 1202.3173)
# ---------------------------------------------------------------------------


def _region_tiles(region) -> list:
    """Stripe-index (i, j) tiles of one schedule region (lower triangle)."""
    if region[0] == "tri":
        _, lo, hi = region
        return [(i, j) for i in range(lo, hi) for j in range(lo, i + 1)]
    _, rlo, rhi, clo, chi = region
    return [(i, j) for i in range(rlo, rhi) for j in range(clo, chi)]


def _region_children(region):
    """One recursion level of the ATA tree in tile space, or None at a leaf.

    A diagonal (triangular) region splits as the paper's ATA recursion:
    ``C11`` (triangle, ceil-half), ``C21`` (the off-diagonal rectangle — the
    two Strassen products of the 4+3 diag/off-diag split), ``C22``
    (triangle). A rectangular region splits 2×2 (its products are plain
    Strassen gemms whose 7-way tree lives *inside* each tile's
    ``strassen_tn`` leaf, below tile granularity).
    """
    if region[0] == "tri":
        _, lo, hi = region
        if hi - lo < 2:
            return None
        mid = lo + (hi - lo + 1) // 2
        return [("tri", lo, mid), ("rect", mid, hi, lo, mid),
                ("tri", mid, hi)]
    _, rlo, rhi, clo, chi = region
    if rhi - rlo < 2 and chi - clo < 2:
        return None
    rows = [(rlo, rhi)] if rhi - rlo < 2 else [
        (rlo, rlo + (rhi - rlo + 1) // 2), (rlo + (rhi - rlo + 1) // 2, rhi)]
    cols = [(clo, chi)] if chi - clo < 2 else [
        (clo, clo + (chi - clo + 1) // 2), (clo + (chi - clo + 1) // 2, chi)]
    return [("rect", a, b, c, d) for a, b in rows for c, d in cols]


def bfs_dfs_assignment(nb: int, pool: int, interleaving: str,
                       *, emit_spans: bool = False):
    """Static BFS/DFS tile ownership over a ``pool``-device task axis.

    The **interleaving-string contract**: ``interleaving`` is a string over
    ``{'B', 'D'}``; character ℓ tags recursion level ℓ of the ATA tree
    *in tile space* (level 0 = the root split of the ``nb``-stripe lower
    triangle). A ``'B'`` (breadth-first, CAPS-style) level splits every
    active device group into disjoint subgroups, one per child subproblem
    (diag/off-diag: two triangles + the C21 rectangle; rectangles split
    2×2), with devices allotted proportionally to child tile counts
    (largest remainder, every nonempty child ≥ 1 device while they last;
    with fewer devices than children, children are LPT-packed onto the
    devices). A ``'D'`` (depth-first) level keeps each group intact — its
    devices sweep that level's subproblems cooperatively. Groups of one
    device, and regions at tile granularity, pass through unchanged, so
    any device count (7-divisible or not) and any string length are valid.
    After the last character each group's tiles are assigned contiguously
    (tri-order) to its devices — a pure-``'D'`` string therefore
    reproduces :func:`ata_tile_parallel`'s contiguous split exactly.

    Returns ``(owned, levels)``: ``owned[dev]`` is the sorted list of
    global tri-order tile ids device ``dev`` computes; ``levels`` is one
    ``{'tag', 'groups'}`` dict per interleaving character (telemetry —
    with ``emit_spans`` each level's split is wrapped in a
    ``distributed.bfs`` / ``distributed.dfs`` obs span).
    """
    if not interleaving or any(c not in "BD" for c in interleaving):
        raise ValueError(
            f"interleaving must be a non-empty string over {{'B','D'}}; "
            f"got {interleaving!r}")
    groups = [([("tri", 0, nb)], list(range(pool)))]
    levels = []

    def split_level(lv: int) -> None:
        nonlocal groups
        new_groups = []
        for regions, devs in groups:
            if len(devs) < 2:
                new_groups.append((regions, devs))
                continue
            kids = []
            for r in regions:
                ch = _region_children(r)
                kids.extend(ch if ch else [r])
            kids = [(k, len(_region_tiles(k))) for k in kids]
            kids = [(k, c) for k, c in kids if c]
            if len(kids) < 2:
                new_groups.append(([k for k, _ in kids], devs))
                continue
            g = len(devs)
            if g >= len(kids):
                total = sum(c for _, c in kids)
                quota = [c * g / total for _, c in kids]
                alloc = [max(1, int(q)) for q in quota]
                while sum(alloc) > g:
                    over = [i for i in range(len(alloc)) if alloc[i] > 1]
                    i = max(over, key=lambda i: alloc[i] - quota[i])
                    alloc[i] -= 1
                while sum(alloc) < g:
                    i = min(range(len(alloc)),
                            key=lambda i: (alloc[i] - quota[i], -quota[i]))
                    alloc[i] += 1
                pos = 0
                for (k, _), a in zip(kids, alloc):
                    new_groups.append(([k], devs[pos:pos + a]))
                    pos += a
            else:
                buckets = [[[], 0] for _ in range(g)]
                for k, c in sorted(kids, key=lambda kc: -kc[1]):
                    b = min(buckets, key=lambda b: b[1])
                    b[0].append(k)
                    b[1] += c
                new_groups.extend(
                    (regs, [dev]) for (regs, _), dev in zip(buckets, devs))
        groups = new_groups

    for lv, ch in enumerate(interleaving):
        if ch == "B":
            if emit_spans:
                with obs.span("distributed.bfs", level=lv):
                    split_level(lv)
            else:
                split_level(lv)
        elif emit_spans:
            with obs.span("distributed.dfs", level=lv, groups=len(groups)):
                pass
        levels.append(dict(tag=ch, groups=len(groups)))

    owned = [[] for _ in range(pool)]
    for regions, devs in groups:
        ts = sorted(i * (i + 1) // 2 + j
                    for r in regions for i, j in _region_tiles(r))
        per = -(-len(ts) // len(devs))
        for idx, dev in enumerate(devs):
            owned[dev] = ts[idx * per:(idx + 1) * per]
    return owned, levels


def ata_bfs_dfs(
    a: jax.Array,
    mesh: Mesh,
    *,
    task_axis: str = "model",
    row_axis: Optional[str] = None,
    interleaving: Optional[str] = None,
    alpha: float = 1.0,
    plan=None,
    n_base: Optional[int] = None,
    variant: Optional[str] = None,
    leaf_dispatch: Optional[str] = None,
    use_strassen: bool = True,
    nb: Optional[int] = None,
    out: str = "dense",
    packed_block: Optional[int] = None,
    acc_dtype=jnp.float32,
) -> Union[jax.Array, SymmetricMatrix]:
    """Distributed ``C = alpha·AᵀA`` under a CAPS-style BFS/DFS schedule.

    The ATA analogue of CAPS (Ballard–Demmel–Holtz–Schwartz, arxiv
    1202.3173): each recursion level of the lower-triangle tile tree is
    tagged BFS (``'B'``) or DFS (``'D'``) by ``interleaving`` (contract:
    see :func:`bfs_dfs_assignment` — e.g. ``"BD"``). BFS levels confine
    each child subproblem (4 sub-ATAs + the C21 Strassen rectangle — the
    diag/off-diag 4+3 split) to a disjoint device subgroup of the task
    axis, so subgroup collectives run on sub-axes of the mesh and never
    cross subgroups; DFS levels keep all of a group's devices cooperating
    on one subproblem, exactly like :func:`ata_tile_parallel`'s contiguous
    sweep. Retrieval is the packed ``SymmetricMatrix`` stack at the root.

    Tile stack (:func:`_tile_stack`, shared with :func:`ata_tile_parallel`):
    where the plan runs the Pallas kernels, a tile is one kernel leaf
    (``min(m_local, w) ≤ n_base``) and the planner's memory figure with the
    copy below fits the device (:func:`_takes_direct`), each device
    computes all its slots in ONE ``gemm_tn_fused`` launch that reads
    stripes ``i`` and ``j`` by block index out of a stripe-major copy of
    its A shard (``m_local·n_pad`` words more per device, relaid once under
    ``distributed.stripes``); dummy slots are dead leaves (all-zero signs),
    whose dot the kernel skips. Otherwise each tile cuts its two stripes and dispatches through
    the planned sequential machinery (``strassen_tn`` with the plan's
    unrolled/batched/fused leaf body). Both give bitwise-equal tiles.

    Communication: any BFS level switches the root exchange to the
    **tri-direct reduce-scatter** — every device stages its partial tiles
    at their global tri positions in a ``T``-padded buffer and one
    ``psum_scatter`` over the merged ``(task, row)`` axes simultaneously
    (a) sums the row-wise partials and (b) deals each device a contiguous
    tri-order chunk of the reduced stack, so the packed retrieval is a
    pure slice and diagonal symmetrization happens locally on the chunk
    (``from_tile_stack(presymmetrized=True)`` skips its cross-shard diag
    gather). The collective payload is one chunk of ``T_pad/(p·d)`` tiles
    per device — versus the psum schedule's full ``t_per``-tile
    all-reduce *plus* an ``nb``-tile diag-gather — at the price of the
    ``T``-tile staging buffer: the classic CAPS memory-for-bandwidth
    trade (BFS = more memory, fewer words; DFS = lean memory, more
    words). A pure-``'D'`` interleaving degenerates to the existing
    schedule — same contiguous assignment, same plain ``psum``, same
    out_specs, bitwise-identical program. Every interleaving is
    value-identical: tile products and their reduction order never depend
    on the tags (the scatter only adds zeros, which is bitwise-neutral),
    so results match :func:`ata_tile_parallel` bitwise in both output
    modes.

    ``interleaving=None`` resolves through the planner
    (``plan.comm_schedule`` — picked per shape/mesh/memory by the α-β
    communication model of ``tune.cost``), falling back to pure DFS.
    Other arguments match :func:`ata_tile_parallel`.
    """
    if out not in ("dense", "packed"):
        raise ValueError(f"unknown output mode {out!r}; use 'dense' or 'packed'")
    m, n = a.shape
    p_task = mesh.shape[task_axis]
    d_row = mesh.shape[row_axis] if row_axis is not None else 1
    if row_axis is not None and m % d_row:
        raise ValueError(
            f"row_axis {row_axis!r} size {d_row} must divide m={m} "
            f"(A is row-sharded P({row_axis!r}, None))"
        )
    if plan is None and n_base is None and variant is None and nb is None \
            and interleaving is None:
        from repro.tune import plan as _plan_fn

        plan = _plan_fn(
            op="ata", m=m, n=n, dtype=str(a.dtype), devices=p_task, out=out,
            row_devices=d_row,
        )
    w = None
    if plan is not None:
        n_base = plan.n_base if n_base is None else n_base
        variant = plan.variant if variant is None else variant
        if leaf_dispatch is None:
            leaf_dispatch = getattr(plan, "leaf_dispatch", None)
        if packed_block is None:
            packed_block = plan.packed_block
        if interleaving is None:
            interleaving = getattr(plan, "comm_schedule", None)
        if plan.algorithm == "dense":
            use_strassen = False
        if nb is None and plan.devices == p_task and plan.n == n and plan.nb \
                and getattr(plan, "row_devices", 1) == d_row:
            nb, w = plan.nb, plan.tile_w
    if interleaving is None:
        interleaving = "D"
    if nb is None:
        if "B" in interleaving and p_task * d_row > 1:
            # BFS tiling: T must divide the merged device pool so the
            # tri-direct reduce-scatter chunks exactly and the packed
            # retrieval is an identity slice (see tune.cost.bfs_tiling)
            from repro.tune.cost import bfs_tiling

            nb, w = bfs_tiling(n, p_task * d_row, devices=p_task, out=out,
                               packed_block=packed_block)
            if packed_block is None:
                packed_block = w
        else:
            nb, w = choose_tiling(n, p_task, out=out,
                                  packed_block=packed_block)
    elif w is None:
        w = -(-n // nb)
        w = -(-w // 8) * 8
    n_pad = nb * w
    t_total = nb * (nb + 1) // 2

    owned, levels = bfs_dfs_assignment(nb, p_task, interleaving,
                                       emit_spans=True)
    pool = p_task * d_row
    scatter = "B" in interleaving and pool > 1
    s_eff = max(len(o) for o in owned)
    # tri-direct staging: pad T to a multiple of the device pool so one
    # reduce-scatter over the merged (task, row) axes lands every device a
    # contiguous tri-order chunk of the fully reduced stack
    t_pad = -(-t_total // pool) * pool
    chunk = t_pad // pool
    # the static slot table the per-device body indexes with its own
    # axis_index: slot_table[dev][q] = global tri-order tile id, -1 = dummy
    import numpy as _np

    slot_table = _np.full((p_task, s_eff), -1, dtype=_np.int32)
    for dev, ts in enumerate(owned):
        slot_table[dev, : len(ts)] = ts
    all_valid = (slot_table >= 0).all(axis=0)  # per-slot: cond-free?
    diag_mask = _np.zeros(t_pad, dtype=bool)
    for i in range(nb):
        diag_mask[i * (i + 1) // 2 + i] = True

    if n_pad > n:
        a = jnp.pad(a, ((0, 0), (0, n_pad - n)))

    obs.metrics.inc("dispatch.ata_bfs_dfs")
    obs.metrics.inc("ata_bfs_dfs.tiles", t_total)
    obs.metrics.inc("ata_bfs_dfs.bfs_levels", interleaving.count("B"))
    obs.metrics.inc("ata_bfs_dfs.dfs_levels", interleaving.count("D"))

    table = jnp.asarray(slot_table)
    diag_tbl = jnp.asarray(diag_mask)
    from repro.launch.mesh import merged_axis

    merged = merged_axis(task_axis, row_axis)
    direct = _takes_direct(
        plan, use_strassen, n_base, m=m, nb=nb, w=w, schedule=interleaving,
        p_task=p_task, d_row=d_row, out=out, itemsize=a.dtype.itemsize,
    )

    def local_fn(a_local):
        pidx = jax.lax.axis_index(task_axis)
        row = jax.lax.dynamic_slice_in_dim(table, pidx, 1, axis=0)[0]
        tiles = _tile_stack(
            a_local, row, list(all_valid), w,
            direct=direct, plan=plan, use_strassen=use_strassen, n_base=n_base,
            variant=variant, leaf_dispatch=leaf_dispatch, acc_dtype=acc_dtype,
        )
        if scatter:
            # BFS redistribution, tri-direct: stage the partial tiles at
            # their global tri positions in a T-padded buffer (one extra
            # sacrificial row swallows the dummy slots), then ONE
            # reduce-scatter over the merged (task, row) axes both sums
            # the row-wise partials and deals every device its contiguous
            # tri-order chunk of the reduced stack — reduction and
            # retrieval re-layout in a single chunk-sized collective.
            ids = jnp.where(row >= 0, row, t_pad)
            buf = jnp.zeros((t_pad + 1, *tiles.shape[1:]), tiles.dtype)
            buf = buf.at[ids].set(tiles)[:t_pad]
            tiles = _collective(
                "distributed.psum_scatter",
                lambda x: jax.lax.psum_scatter(x, merged, scatter_dimension=0,
                                               tiled=True),
                buf, axis=str(merged), out="packed")
            # local diagonal symmetrization: the chunk's global tile ids
            # are axis_index-affine, so diag membership is a tiny static
            # table lookup — from_tile_stack can then skip its cross-shard
            # _symmetrize_diag gather (presymmetrized=True).
            k = jax.lax.axis_index(task_axis)
            if row_axis is not None:
                k = k * d_row + jax.lax.axis_index(row_axis)
            dm = jnp.take(diag_tbl, k * chunk + jnp.arange(chunk))
            tiles = jnp.where(dm[:, None, None], sym_tile(tiles), tiles)
        elif row_axis is not None:
            tiles = _collective("distributed.psum",
                                lambda x: jax.lax.psum(x, row_axis), tiles,
                                axis=row_axis, out="packed")
        return tiles

    in_spec = P(row_axis, None) if row_axis else P(None, None)
    out_spec = (P(merged, None, None) if scatter
                else P(task_axis, None, None))
    with obs.span("distributed.ata", schedule=interleaving, tiles=t_total):
        tiles = jax.shard_map(
            local_fn, mesh=_auto_axes(mesh), in_specs=(in_spec,),
            out_specs=out_spec,
        )(a)
        # either way the global stack is the tri-order prefix: scatter path
        # by construction (chunk k holds tiles [k·chunk, (k+1)·chunk)), psum
        # path because contiguous per-task assignment puts task t's tiles at
        # [t·s_eff, …) with dummies trailing — retrieval is a pure slice.
        return _assemble(tiles, n, nb, packed_block, alpha, out,
                         presymmetrized=scatter)


def tile_parallel_device_flops(
    m: int,
    n: int,
    p: int,
    *,
    nb: Optional[int] = None,
    n_base: Optional[int] = None,
    use_strassen: Optional[bool] = None,
    dtype: str = "float32",
    out: str = "dense",
    packed_block: Optional[int] = None,
) -> list:
    """Exact per-device flops of :func:`ata_tile_parallel`'s masked schedule.

    Device ``d`` computes its valid contiguous slots only — dummy slots are
    cond-masked zero tiles, not recomputed clamps — so the per-device counts
    are ``t_per`` (or fewer) uniform-tile flop counts and the total over
    devices is exactly ``T`` tiles' worth: the LPT model of ``T`` equal
    tasks. Mirrors the tile compute path via the reference counters —
    including the tunable resolution: unpinned ``n_base``/``use_strassen``
    resolve through the same planner front door the execution path
    consults, so the model counts what the default dispatch actually runs
    (pass the operand's ``dtype`` — the plan, and hence the recursion, is
    keyed on it — and the dispatch's ``out``/``packed_block``: the packed
    mode's tiling can snap to the packed block grid, changing the stripe
    width the flop model must mirror).
    """
    from repro.core.reference import classical_gemm_flops, strassen_tn_flops

    if n_base is None or use_strassen is None:
        from repro.tune import plan as _plan_fn

        pl = _plan_fn(op="ata", m=m, n=n, dtype=dtype, devices=p, out=out)
        n_base = pl.n_base if n_base is None else n_base
        use_strassen = (
            (pl.algorithm != "dense") if use_strassen is None else use_strassen
        )
    if nb is None:
        nb, w = choose_tiling(n, p, out=out, packed_block=packed_block)
    else:
        w = -(-n // nb)
        w = -(-w // 8) * 8
    t_total = nb * (nb + 1) // 2
    t_per = -(-t_total // p)
    tile = (
        strassen_tn_flops(m, w, w, n_base)
        if use_strassen
        else classical_gemm_flops(m, w, w)
    )
    return [
        tile * max(0, min(t_per, t_total - d * t_per)) for d in range(p)
    ]


# ---------------------------------------------------------------------------
# colshard gemm: C = AᵀB with B column-sharded (disjoint C column stripes)
# ---------------------------------------------------------------------------


def gemm_tn_colshard(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    *,
    task_axis: str = "model",
    row_axis: Optional[str] = None,
    plan=None,
    n_base: Optional[int] = None,
    variant: Optional[str] = None,
    leaf_dispatch: Optional[str] = None,
    use_strassen: bool = True,
) -> jax.Array:
    """Distributed ``C = AᵀB``: each device owns C's column stripe for its
    B shard — the FastStrassen leaves of the task tree, collision-free.
    Leaf tunables (including ``leaf_dispatch`` — the per-device stripe
    product reuses the batched or fused leaf formulation when the plan
    picks it) resolve through the planner unless pinned."""
    m, n = a.shape
    mb, k = b.shape
    if m != mb:
        raise ValueError(f"contraction mismatch {a.shape} vs {b.shape}")
    p_task = mesh.shape[task_axis]
    if k % p_task:
        # the requirement runs device→columns: every device of the task
        # axis owns one equal column stripe of C.
        raise ValueError(
            f"task axis {task_axis!r} size {p_task} must divide k={k} "
            f"(B is column-sharded P(..., {task_axis!r}))"
        )
    if row_axis is not None:
        p_row = mesh.shape[row_axis]
        if m % p_row:
            # validated here, with the same orientation, instead of letting
            # shard_map fail opaquely on an indivisible in_spec.
            raise ValueError(
                f"row_axis {row_axis!r} size {p_row} must divide the "
                f"contraction dim m={m} (A and B are row-sharded "
                f"P({row_axis!r}, ...))"
            )
    if plan is not None:
        n_base = plan.n_base if n_base is None else n_base
        variant = plan.variant if variant is None else variant
        if leaf_dispatch is None:
            leaf_dispatch = getattr(plan, "leaf_dispatch", None)
        if plan.algorithm == "dense":
            use_strassen = False
    # unpinned n_base/variant fall through to strassen_tn, which self-plans
    # on the per-device leaf shape (m, n, k/p) — every dispatch is planned.

    obs.metrics.inc("dispatch.gemm_tn_colshard")
    _, base_dot = _plan_base_fns(plan, None, None)

    def local_fn(a_local, b_local):
        with obs.span("distributed.colshard_body", use_strassen=use_strassen):
            if use_strassen:
                c_local = strassen_tn(
                    a_local, b_local, n_base=n_base, variant=variant,
                    leaf_dispatch=leaf_dispatch, base_dot=base_dot,
                )
            else:
                c_local = jax.lax.dot_general(
                    a_local, b_local, (((0,), (0,)), ((), ())),
                    precision=dot_precision(a_local, b_local),
                    preferred_element_type=jnp.float32,
                )
        if row_axis is not None:
            c_local = _collective("distributed.psum",
                                  lambda x: jax.lax.psum(x, row_axis), c_local,
                                  axis=row_axis, out="dense")
        return c_local

    row_spec = row_axis if row_axis else None
    return jax.shard_map(
        local_fn,
        mesh=_auto_axes(mesh),
        in_specs=(P(row_spec, None), P(row_spec, task_axis)),
        out_specs=P(None, task_axis),
    )(a, b)
