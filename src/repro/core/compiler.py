"""Physical-plan compiler: costed physical plan → one jitted SPMD program.

The AsterixDB analogue of "ship the SQL++ string, get an optimized Hyracks
job": the physical plan (core/physical.py, chosen by the cost-based planner
in core/physical_planner.py) lowers to a closed JAX function over (dataset
columns, literal params) and jits once per *physical* fingerprint — literal
values are runtime params, so randomized predicates reuse the executable
(the prepared-statement effect the paper gets from AsterixDB's plan cache).

The three execution modes are **lowering strategies**, not branches inside
operator lowerings:

  * ``gspmd``     — :class:`LoweringStrategy`: plain jnp ops; under jit XLA
    GSPMD inserts collectives (the paper-faithful baseline).
  * ``shard_map`` — :class:`ShardMapStrategy`: relational operators from
    engine/distributed.py with hand-placed minimal collectives.
  * ``kernel``    — same two strategies; what makes kernel mode different is
    the *planner* emitting kernel physical operators (KernelRangeCount,
    KernelSegmentAgg, kernel JoinCount, block-topk selection), which every
    strategy knows how to launch (locally or composed via shard_map).

Each ``_lower_*`` function handles exactly one physical operator and calls
only ``ctx.strategy`` primitives — there is no ``ctx.mode`` branching inside
lowerings.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import physical as PH
from repro.core.catalog import INTERNAL_COLUMNS, Catalog
from repro.core.expr import collect_params, param_values
from repro.engine import physical
from repro.kernels import ops
from repro.runtime import telemetry as tel


# -- lowering strategies ------------------------------------------------------


class LoweringStrategy:
    """Single-program lowering: plain jnp over (possibly sharded) arrays —
    under jit, XLA GSPMD inserts any needed collectives."""

    def __init__(self, kernel_backend: Optional[str] = None,
                 kernel_interpret: Optional[bool] = None):
        self.kernel_backend = kernel_backend
        # None = auto-detect per kernels/ops: compiled Pallas on TPU,
        # interpret mode elsewhere; a Session(kernel_interpret=...) override
        # forces one or the other (debugging / TPU bring-up).
        self.kernel_interpret = kernel_interpret

    def count(self, mask):
        return jnp.sum(mask, dtype=jnp.int32)

    def agg(self, env, mask, op, column):
        return physical.agg_scalar(env, mask, op, column)

    def limit(self, env, mask, n):
        return physical.limit(env, mask, n)

    def topk(self, env, mask, key, k, ascending, select):
        return physical.topk(env, mask, key, k, ascending, select=select)

    def group_agg(self, env, mask, key, lo, num_groups, aggs):
        return physical.group_agg(env, mask, key, lo, num_groups, aggs)

    def kernel_group_agg(self, gid, values, num_groups, n, op,
                         block_ids: Optional[tuple] = None,
                         shard_blocks=None):
        assert shard_blocks is None, \
            "per-shard grids need the shard_map strategy"
        return ops.segment_agg(values, gid, num_groups, n, op=op,
                               backend=self.kernel_backend,
                               block_ids=block_ids,
                               interpret=self.kernel_interpret)

    def kernel_filter_count(self, mat, bounds,
                            block_ids: Optional[tuple] = None,
                            shard_blocks=None):
        assert shard_blocks is None, \
            "per-shard grids need the shard_map strategy"
        return ops.filter_count(mat, bounds, mat.shape[1],
                                backend=self.kernel_backend,
                                block_ids=block_ids,
                                interpret=self.kernel_interpret)

    def index_count(self, ix_keys, valid, lo, hi):
        from repro.engine.index import index_count_local
        nv = jnp.sum(valid, dtype=jnp.int32)
        return index_count_local(ix_keys, nv, lo, hi)

    def shadow_count(self, ix_keys, valid, anti_keys, lo, hi):
        from repro.engine.index import shadow_count_local
        nv = jnp.sum(valid, dtype=jnp.int32)
        return shadow_count_local(ix_keys, nv, anti_keys, lo, hi)

    def join_count(self, lkey, lmask, rkey, rmask, presorted):
        if presorted:
            # index order: valid keys ascending, padding at +inf tail
            n_r = jnp.sum(rmask, dtype=jnp.int32)
            lo = jnp.searchsorted(rkey, lkey, side="left")
            hi = jnp.searchsorted(rkey, lkey, side="right")
            hi = jnp.minimum(hi, n_r)
            cnt = jnp.where(lmask, jnp.maximum(hi - lo, 0), 0)
            return jnp.sum(cnt, dtype=jnp.int32)
        return physical.join_count(lkey, lmask, rkey, rmask)

    def kernel_join_count(self, lkey, lmask, rkey, rmask, presorted):
        ls = ops.sort_join_keys(lkey, lmask)
        rs = ops.sort_join_keys(rkey, rmask, presorted=presorted)
        nl = jnp.sum(lmask, dtype=jnp.int32)
        nr = jnp.sum(rmask, dtype=jnp.int32)
        cnt = ops.merge_join_count(ls, rs, nl, nr,
                                   backend=self.kernel_backend)
        return cnt.astype(jnp.int32)


class ShardMapStrategy(LoweringStrategy):
    """Hand-placed minimal collectives: each relational primitive runs
    per-shard inside shard_map with an explicit psum/pmax/gather merge
    (engine/distributed.py)."""

    def __init__(self, mesh, data_axes, kernel_backend: Optional[str] = None,
                 kernel_interpret: Optional[bool] = None):
        super().__init__(kernel_backend, kernel_interpret)
        self.mesh, self.data_axes = mesh, data_axes

    def count(self, mask):
        from repro.engine import distributed as D
        return D.dist_count(self.mesh, self.data_axes, mask)

    def agg(self, env, mask, op, column):
        from repro.engine import distributed as D
        if op == "count":
            return D.dist_count(self.mesh, self.data_axes, mask)
        return D.dist_agg(self.mesh, self.data_axes, op, env[column], mask)

    def limit(self, env, mask, n):
        from repro.engine import distributed as D
        return D.dist_limit(self.mesh, self.data_axes, env, mask, n)

    def topk(self, env, mask, key, k, ascending, select):
        from repro.engine import distributed as D
        return D.dist_topk(self.mesh, self.data_axes, env, mask, key, k,
                           ascending, select=select)

    def group_agg(self, env, mask, key, lo, num_groups, aggs):
        from repro.engine import distributed as D
        value_cols = {c: env[c] for _, _, c in aggs if c}
        out, gmask = D.dist_group_agg(self.mesh, self.data_axes, env[key],
                                      mask, lo, num_groups, aggs, value_cols)
        out[key] = out.pop("__key__")
        return out, gmask

    def kernel_group_agg(self, gid, values, num_groups, n, op,
                         block_ids: Optional[tuple] = None,
                         shard_blocks=None):
        from repro.engine import distributed as D
        return D.dist_kernel_group_agg(self.mesh, self.data_axes, gid, values,
                                       num_groups, op=op,
                                       backend=self.kernel_backend,
                                       block_ids=block_ids,
                                       shard_blocks=shard_blocks,
                                       interpret=self.kernel_interpret)

    def kernel_filter_count(self, mat, bounds,
                            block_ids: Optional[tuple] = None,
                            shard_blocks=None):
        from repro.engine import distributed as D
        return D.dist_kernel_filter_count(self.mesh, self.data_axes, mat,
                                          bounds, backend=self.kernel_backend,
                                          block_ids=block_ids,
                                          shard_blocks=shard_blocks,
                                          interpret=self.kernel_interpret)

    def index_count(self, ix_keys, valid, lo, hi):
        from repro.engine import distributed as D
        return D.dist_index_count(self.mesh, self.data_axes, ix_keys, valid,
                                  lo, hi)

    def shadow_count(self, ix_keys, valid, anti_keys, lo, hi):
        from repro.engine import distributed as D
        return D.dist_shadow_count(self.mesh, self.data_axes, ix_keys, valid,
                                   anti_keys, lo, hi)

    def join_count(self, lkey, lmask, rkey, rmask, presorted):
        from repro.engine import distributed as D
        return D.dist_join_count(self.mesh, self.data_axes, lkey, lmask,
                                 rkey, rmask, presorted_right=presorted)

    def kernel_join_count(self, lkey, lmask, rkey, rmask, presorted):
        from repro.engine import distributed as D
        return D.dist_kernel_join_count(self.mesh, self.data_axes, lkey,
                                        lmask, rkey, rmask,
                                        presorted_right=presorted,
                                        backend=self.kernel_backend)


def make_strategy(ctx: "ExecContext") -> LoweringStrategy:
    """The ONLY place execution mode is consulted at lowering time: pick the
    collective-placement strategy. Operator choice already happened in the
    planner."""
    if ctx.mode in ("shard_map", "kernel") and ctx.mesh is not None:
        return ShardMapStrategy(ctx.mesh, ctx.data_axes, ctx.kernel_backend,
                                ctx.kernel_interpret)
    return LoweringStrategy(ctx.kernel_backend, ctx.kernel_interpret)


@dataclasses.dataclass
class ExecContext:
    catalog: Catalog
    mesh: Any = None            # jax Mesh when distributed
    data_axes: tuple = ("data",)
    mode: str = "gspmd"         # gspmd | shard_map | kernel
    kernel_backend: Optional[str] = None  # kernels/ops dispatch: None|xla|pallas
    kernel_interpret: Optional[bool] = None  # None = auto (TPU compiled)
    strategy: Optional[LoweringStrategy] = None

    def __post_init__(self):
        if self.strategy is None:
            self.strategy = make_strategy(self)


@dataclasses.dataclass
class CompiledQuery:
    plan: Any                   # the optimized *logical* plan (provenance)
    physical: PH.PhysOp         # the costed physical plan that was lowered
    fingerprint: str            # physical fingerprint (executable dedup key)
    kind: str                   # scalar | table | grouped
    fn: Callable                # jitted: (tables, params) -> result
    leaf_keys: list             # dataset keys feeding `tables` (pruned runs excluded)
    lits: list                  # literal slots (physical plan order)
    raw_fn: Callable = None     # unjitted build (jaxpr inspection in tests)
    anti_keys: list = dataclasses.field(default_factory=list)
    #                             components whose sorted anti-key arrays the
    #                             plan subtracts with (may include runs whose
    #                             MATTER was zone-pruned — their tombstones
    #                             still annihilate into older components)
    launches: dict = dataclasses.field(default_factory=dict)
    #                             kernel.* series id -> count per execution,
    #                             recorded when ``fn`` traces

    def gather_tables(self, catalog: Catalog) -> dict:
        tables = {}
        for key in self.leaf_keys:
            ds = catalog.get(*key)
            tables[f"{key[0]}.{key[1]}"] = dict(ds.table.columns)
            for ix in ds.indexes.values():
                if ix.sorted_keys is not None:
                    tables[f"{key[0]}.{key[1]}"][f"__ix_{ix.column}__"] = ix.sorted_keys
                    tables[f"{key[0]}.{key[1]}"][f"__ixid_{ix.column}__"] = ix.row_ids
        for key in self.anti_keys:
            ds = catalog.get(*key)
            tables[f"anti:{key[0]}.{key[1]}"] = ds.anti_keys_arr
        return tables

    def run(self, catalog: Catalog, lits=None, params=None):
        """``params``: pre-bound literal values in slot order (the Session's
        plan cache computes them via its literal binding). ``lits``: literal
        slots from the *current* plan instance — on a plan-cache hit the
        executable is reused but the fresh literal values must be bound
        (same fingerprint ⇒ same slot order)."""
        if params is None:
            params = param_values(lits if lits is not None else self.lits)
        return self.call(self.gather_tables(catalog), params)

    def call(self, tables: dict, params):
        """Dispatch ``fn`` (the result may still be computing) and count
        the kernel launches its trace recorded, once per execution."""
        out = self.fn(tables, params)
        if self.launches:
            tel.registry().inc_series(self.launches)
        return out


def program_name(kind: str, fingerprint: str) -> str:
    """Name of a query's jitted program, which its XLA module takes
    (``jit_<name>``): the terminal kind and 8 hex digits of a hash of the
    physical fingerprint, so a device trace names the query shape."""
    digest = hashlib.sha1(fingerprint.encode()).hexdigest()[:8]
    return f"aframe_{kind}_{digest}"


def compile_physical(logical, phys: PH.PhysOp, ctx: ExecContext) -> CompiledQuery:
    """Lower one physical plan into a jitted executable."""
    leaf_keys = PH.scan_leaves(phys)
    lits = collect_params(PH.all_exprs(phys))
    kind, build = _lower_terminal(phys, ctx)
    fp = phys.fingerprint()
    launches: dict = {}

    def program(tables, params):
        with ops.recording_launches(launches):
            return build(tables, params)
    program.__name__ = program.__qualname__ = program_name(kind, fp)
    return CompiledQuery(logical, phys, fp, kind, jax.jit(program),
                         leaf_keys, lits, raw_fn=build,
                         anti_keys=PH.anti_leaves(phys), launches=launches)


def compile_plan(opt_plan, ctx: ExecContext, *, enable_index: bool = True,
                 enable_prune: bool = True,
                 enable_block_skip: bool = True) -> CompiledQuery:
    """Convenience one-shot path (``Session.persist``, tests): cost-plan the
    optimized logical plan — pruning decided from its own literal values —
    then lower. The knobs mirror the Session's planner settings."""
    from repro.core.expr import ordered_lits
    from repro.core.physical_planner import (NO_PRUNE, build_pruner,
                                             plan_physical)
    from repro.core import plan as P

    raw_lits = ordered_lits(P.all_exprs(opt_plan))
    decisions = NO_PRUNE
    if enable_prune:
        from repro.core.stats import mesh_shards

        pruner = build_pruner(opt_plan, ctx.catalog, raw_lits,
                              n_shards=mesh_shards(ctx.mesh, ctx.data_axes))
        decisions = pruner.decide([l.value for l in raw_lits],
                                  block_skip=enable_block_skip)
    phys = plan_physical(opt_plan, ctx.catalog, mode=ctx.mode,
                         decisions=decisions, enable_index=enable_index)
    return compile_physical(opt_plan, phys, ctx)


def _result_rows(kind: str, out) -> int:
    """Actual row count of one lowered result: live mask sum for streams and
    groups, 1 for a scalar dict."""
    if kind in ("table", "grouped"):
        return int(np.asarray(out[1]).sum())
    return 1


def profile_physical(phys: PH.PhysOp, ctx: ExecContext, tables: dict,
                     params) -> dict:
    """Per-operator measurement for ``explain(analyze=True)``.

    The compiled executable is ONE fused jitted program — XLA gives no
    per-operator attribution — so profiling lowers each node's *subtree*
    standalone and executes it eagerly (unjitted, ``block_until_ready``
    synchronized). Self time = subtree total − Σ direct-child subtree
    totals, clamped at 0 (eager timing noise can invert tiny nodes). Row
    counts are exact: same lowering, same inputs as the jitted run.
    O(nodes · subtree cost) — fine at these plan sizes, and only paid when
    the user explicitly asks to analyze.

    Returns ``{"nodes": {id(node): {kind, total_seconds, self_seconds,
    rows}}}`` — the dict ``format_plan(root, analyze=...)`` renders."""
    nodes: dict[int, dict] = {}
    for node in PH.walk(phys):
        try:
            kind, build = _lower_terminal(node, ctx)
        except NotImplementedError:  # pragma: no cover - defensive
            continue
        with tel.span("profile.operator", op=type(node).__name__):
            t0 = time.perf_counter()
            out = jax.block_until_ready(build(tables, params))
            dt = time.perf_counter() - t0
        nodes[id(node)] = {"kind": kind, "total_seconds": dt,
                           "rows": _result_rows(kind, out)}
    for node in PH.walk(phys):
        m = nodes.get(id(node))
        if m is None:
            continue
        kids = sum(nodes[id(c)]["total_seconds"] for c in node.children
                   if id(c) in nodes)
        m["self_seconds"] = max(m["total_seconds"] - kids, 0.0)
    return {"nodes": nodes}


# -- streaming lowering -------------------------------------------------------


def _env_of(cols: dict, open_cast: bool):
    from repro.engine.table import is_lane_column

    env = {k: v for k, v in cols.items()
           if k not in INTERNAL_COLUMNS and not k.startswith("__ix")}
    if open_cast:  # schema-on-read: pay a widen/cast per access — but the
        # derived string lanes stay integer (dict-id remaps index with them)
        env = {k: (v.astype(jnp.float32) if jnp.issubdtype(v.dtype, jnp.integer)
                   and v.ndim == 1 and not is_lane_column(k) else v)
               for k, v in env.items()}
    mask = cols.get("__valid__",
                    jnp.ones((next(iter(env.values())).shape[0],), jnp.bool_))
    return env, mask


def _shadowed(tables: dict, keys, shadow_sources) -> "jax.Array":
    """True where a row's primary key appears in any newer component's
    sorted anti-key set — the newest-wins subtraction every matter stream
    applies. One batched binary search per tombstone set; mode-independent
    (the anti arrays are replicated, so gspmd/shard_map/kernel agree
    bit-for-bit)."""
    hit = None
    for dv, name in shadow_sources:
        ak = tables[f"anti:{dv}.{name}"]
        k = keys.astype(ak.dtype)
        pos = jnp.minimum(jnp.searchsorted(ak, k, side="left"),
                          ak.shape[0] - 1)
        h = ak[pos] == k
        hit = h if hit is None else (hit | h)
    return hit


def _block_gather(blocks: Optional[tuple], zone_block: int,
                  n_shards: int = 1, blocks_per_shard: int = 0,
                  rows_per_shard: int = 0, pad_multiple: int = 1):
    """Static-slice gather of the surviving row blocks (ascending ids keep
    the original row order). None = identity. Used by the generic stream
    path — the gspmd/shard_map analogue of driving the kernel grid through
    the block-id list.

    With ``n_shards > 1`` flat block ids address per-shard local tiles
    (``s * blocks_per_shard + j`` = shard ``s``'s local block ``j``); the
    slice is computed inside shard ``s``'s contiguous row chunk and a
    trailing partial block clips at the chunk boundary, so a gather never
    straddles shards. ``pad_multiple`` zero-pads the gathered length up to a
    multiple (shard_map operators split rows evenly over the mesh): pad rows
    carry a False mask (bool zero), so every mask-aware operator ignores
    them."""
    if blocks is None:
        return lambda col: col
    spans = []
    for b in blocks:
        if n_shards <= 1:
            spans.append((b * zone_block, (b + 1) * zone_block))
        else:
            s, j = divmod(b, blocks_per_shard)
            base = s * rows_per_shard
            spans.append((base + j * zone_block,
                          base + min((j + 1) * zone_block, rows_per_shard)))

    def sel(col):
        parts = [col[lo:hi] for lo, hi in spans]
        out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
        if pad_multiple > 1:
            pad = (-out.shape[0]) % pad_multiple
            if pad:
                out = jnp.pad(out, [(0, pad)] + [(0, 0)] * (out.ndim - 1))
        return out
    return sel


def _stream_pad(ctx: ExecContext) -> int:
    """Row-count multiple gathered streams must keep: shard_map operators
    split their inputs evenly over the mesh's data axes."""
    if isinstance(ctx.strategy, ShardMapStrategy):
        from repro.core.stats import mesh_shards
        return mesh_shards(ctx.mesh, ctx.data_axes)
    return 1


def _lower_stream(node: PH.PhysOp, ctx: ExecContext) -> Callable:
    """Returns fn(tables, params) -> (env, mask). Filters never compact
    (selection-vector execution; DESIGN.md §2)."""
    if isinstance(node, PH.TableScan):
        key = f"{node.dataverse}.{node.dataset}"
        open_cast = node.open_cast
        shadow, key_col = node.shadow_sources, node.key_col
        sel = _block_gather(node.block_ids, node.zone_block,
                            *node.shard_layout(), pad_multiple=_stream_pad(ctx))

        def fn(tables, params):
            env, mask = _env_of(tables[key], open_cast)
            env = {k: sel(v) for k, v in env.items()}
            mask = sel(mask)
            if shadow:
                mask = mask & ~_shadowed(tables, sel(tables[key][key_col]),
                                         shadow)
            return env, mask
        return fn

    if isinstance(node, PH.IndexProbe):
        key = f"{node.dataverse}.{node.dataset}"
        open_cast = node.open_cast
        shadow, key_col = node.shadow_sources, node.key_col
        # the probe inherits its Scan site's surviving-block list: rows in
        # skipped blocks provably fail the very conjuncts that bound the
        # probe, so gathering first shrinks what the range mask touches.
        sel = _block_gather(node.block_ids, node.zone_block,
                            *node.shard_layout(), pad_multiple=_stream_pad(ctx))

        def fn(tables, params):
            env, mask = _env_of(tables[key], open_cast)
            env = {k: sel(v) for k, v in env.items()}
            mask = sel(mask)
            if shadow:
                mask = mask & ~_shadowed(tables, sel(tables[key][key_col]),
                                         shadow)
            keys_col = env[node.index_col]
            lo = node.lo.evaluate(env, params) if node.lo is not None else None
            hi = node.hi.evaluate(env, params) if node.hi is not None else None
            mask = physical.index_range_mask(keys_col, mask, lo, hi)
            if node.residual is not None:
                mask = mask & node.residual.evaluate(env, params)
            return env, mask
        return fn

    if isinstance(node, PH.PrunedUnionRuns):
        kids = [_lower_stream(c, ctx) for c in node.children]
        if len(kids) == 1:
            return kids[0]

        def fn(tables, params):
            envs, masks = [], []
            for k in kids:
                e, m = k(tables, params)
                envs.append(e)
                masks.append(m)
            names = list(envs[0])
            env = {n: jnp.concatenate([e[n] for e in envs], axis=0)
                   for n in names}
            return env, jnp.concatenate(masks, axis=0)
        return fn

    if isinstance(node, PH.DictRemapCols):
        child = _lower_stream(node.children[0], ctx)
        key, lane = node.key, node.lane
        remap = np.asarray(node.remap, np.int32)

        def fn(tables, params):
            env, mask = child(tables, params)
            env = dict(env)
            lane_col = env.pop(lane)
            if remap.size == 0:
                # empty local dictionary: the component has no live string
                # rows, so every row is masked — any id is fine.
                env[key] = jnp.zeros_like(lane_col)
            else:
                # dead rows carry id -1: clamp to 0 — they map to SOME valid
                # union id, but their mask is False so they weigh nothing.
                env[key] = jnp.take(jnp.asarray(remap),
                                    jnp.maximum(lane_col, 0).astype(jnp.int32))
            return env, mask
        return fn

    if isinstance(node, PH.FullScanFilter):
        child = _lower_stream(node.children[0], ctx)

        def fn(tables, params):
            env, mask = child(tables, params)
            return env, mask & node.predicate.evaluate(env, params)
        return fn

    if isinstance(node, PH.ProjectCols):
        child = _lower_stream(node.children[0], ctx)
        outputs = node.outputs

        def fn(tables, params):
            env, mask = child(tables, params)
            return {name: e.evaluate(env, params) for name, e in outputs}, mask
        return fn

    if isinstance(node, PH.LimitRows):
        child = _lower_stream(node.children[0], ctx)

        def fn(tables, params):
            env, mask = child(tables, params)
            return ctx.strategy.limit(env, mask, node.n)
        return fn

    if isinstance(node, PH.TopKSelect):
        child = _lower_stream(node.children[0], ctx)
        # one lowering, parameterized by the selection primitive: the planner
        # swaps in the block_topk Pallas kernel, everything else is shared.
        select = physical.kernel_topk_select(ctx.kernel_backend) \
            if node.kernel else physical._select_topk

        def fn(tables, params):
            env, mask = child(tables, params)
            return ctx.strategy.topk(env, mask, node.key, node.k,
                                     node.ascending, select)
        return fn

    if isinstance(node, PH.SortRows):
        child = _lower_stream(node.children[0], ctx)

        def fn(tables, params):
            env, mask = child(tables, params)
            return physical.sort_full(env, mask, node.key, node.ascending)
        return fn

    if isinstance(node, PH.WindowEval):
        from repro.core.window import execute_window

        child = _lower_stream(node.children[0], ctx)

        def fn(tables, params):
            env, mask = child(tables, params)
            return execute_window(env, mask, node.window)
        return fn

    if isinstance(node, PH.JoinGather):
        # build-key uniqueness/disjointness was proven by the planner
        lchild = _lower_stream(node.children[0], ctx)
        rchild = _lower_stream(node.children[1], ctx)

        def fn(tables, params):
            lenv, lm = lchild(tables, params)
            renv, rm = rchild(tables, params)
            return physical.join_materialize(lenv, lm, renv, rm,
                                             node.left_on, node.right_on)
        return fn

    if isinstance(node, (PH.GroupAggGeneric, PH.KernelSegmentAgg)):
        return _lower_groupagg(node, ctx)

    raise NotImplementedError(f"stream lowering for {type(node).__name__}")


def _lower_groupagg(node, ctx: ExecContext) -> Callable:
    aggs = [(s.out_name, s.op, s.column) for s in node.aggs]
    if isinstance(node, PH.KernelSegmentAgg):
        comps = [_lower_stream(c, ctx) for c in node.children]
        inner = _lower_kernel_segment_agg(node, ctx, comps, aggs)
    else:
        child = _lower_stream(node.children[0], ctx)
        key, lo, num_groups = node.key, node.lo, node.num_groups

        def inner(tables, params):
            env, mask = child(tables, params)
            return ctx.strategy.group_agg(env, mask, key, lo, num_groups, aggs)

    key_values = getattr(node, "key_values", None)
    if key_values is None:
        return inner

    # string group-by: the machinery above grouped over union-dictionary ids
    # (DictRemapCols remapped each component below the concat). Decode the
    # surviving ids back to the encoded (G, 16) string rows at the result
    # boundary — identical in all three modes because every path returns the
    # group id itself as the key column.
    from repro.engine.table import encode_strings

    enc = np.asarray(encode_strings(list(key_values)))
    out_key = node.key

    def fn(tables, params):
        out, gmask = inner(tables, params)
        out = dict(out)
        out[out_key] = jnp.take(jnp.asarray(enc),
                                out[out_key].astype(jnp.int32), axis=0)
        return out, gmask
    return fn


def _lower_kernel_segment_agg(node: PH.KernelSegmentAgg, ctx: ExecContext,
                              comps: list, aggs: list) -> Callable:
    """One lowered stream per LSM component (a single entry for a plain
    dataset). Each component runs its own kernel launches — one fused
    one-hot-matmul for the sum family, one select-and-reduce per extreme
    family — and the (G, C) partials merge with +/max/min, exactly the merge
    a compaction-time recompute would produce. The planner proved f32
    exactness; count/sum/mean fuse into a single (BLOCK, C) value tile
    (col 0 counts, cols 1.. sum the value columns)."""
    key, lo, num_groups = node.key, node.lo, node.num_groups
    comp_blocks = node.comp_blocks or tuple(None for _ in comps)
    # resolve each component's hoisted block list ONCE at lowering time:
    # single-shard layouts keep the static zone-block tuple (the grid bakes
    # it in); multi-shard layouts expand to the per-shard (-1-padded)
    # kernel-block matrix each shard's launch scalar-prefetches.
    resolved: list[tuple] = []
    for blk in comp_blocks:
        if blk is None or blk[0] is None:
            resolved.append((None, None))
            continue
        ids, zb = blk[0], blk[1]
        nsh, bp, rps = (blk[2:5] if len(blk) >= 5 else (1, 0, 0))
        if nsh > 1:
            from repro.kernels.segment_agg import BLOCK as _SA_BLOCK
            resolved.append((None, ops.shard_block_arrays(
                ids, zb, _SA_BLOCK, nsh, bp, rps)))
        else:
            resolved.append((ids, None))
    vcols: list[str] = []   # distinct sum-family value columns, first-use order
    xcols: dict[str, list[str]] = {"max": [], "min": []}
    for _, op, col in aggs:
        if op in ("sum", "mean") and col not in vcols:
            vcols.append(col)
        elif op in ("max", "min") and col not in xcols[op]:
            xcols[op].append(col)

    def launch(gid, cols_f32, n, op, block_ids, shard_blocks):
        values = jnp.stack(cols_f32, axis=1)  # (n, C)
        return ctx.strategy.kernel_group_agg(gid, values, num_groups, n, op,
                                             block_ids=block_ids,
                                             shard_blocks=shard_blocks)

    def fn(tables, params):
        sums = maxs = mins = None
        key_dtype = val_dtypes = None
        for comp, (block_ids, shard_blocks) in zip(comps, resolved):
            env, mask = comp(tables, params)
            # block_ids/shard_blocks were hoisted off the component's
            # TableScan: the stream stays full-length and the segment_agg
            # grid itself skips pruned tiles (rows there are already masked
            # out by the filter the list came from).
            key_col = env[key]
            key_dtype = key_col.dtype
            val_dtypes = {c: env[c].dtype for _, _, c in aggs if c}
            # dead rows get gid -1: the kernel's live-check drops them, so an
            # arbitrary (non-prefix) mask needs no compaction.
            gid = jnp.where(mask, (key_col - lo).astype(jnp.int32), -1)
            n = mask.shape[0]
            tiles = [jnp.ones(mask.shape, jnp.float32)]
            tiles += [env[c].astype(jnp.float32) for c in vcols]
            part = launch(gid, tiles, n, "sum", block_ids, shard_blocks)
            sums = part if sums is None else sums + part
            if xcols["max"]:
                part = launch(gid, [env[c].astype(jnp.float32)
                                    for c in xcols["max"]], n, "max",
                              block_ids, shard_blocks)
                maxs = part if maxs is None else jnp.maximum(maxs, part)
            if xcols["min"]:
                part = launch(gid, [env[c].astype(jnp.float32)
                                    for c in xcols["min"]], n, "min",
                              block_ids, shard_blocks)
                mins = part if mins is None else jnp.minimum(mins, part)
        counts = sums[:, 0].astype(jnp.int32)
        out = {key: jnp.arange(lo, lo + num_groups, dtype=key_dtype)}
        for out_name, op, col in aggs:
            if op == "count":
                out[out_name] = counts
            elif op == "sum":
                out[out_name] = sums[:, 1 + vcols.index(col)].astype(val_dtypes[col])
            elif op == "mean":  # exact-integer f32 sum / count, as generic
                out[out_name] = sums[:, 1 + vcols.index(col)] / jnp.maximum(counts, 1)
            else:  # max/min: empty groups hold ±inf — pin before the int cast
                src = maxs if op == "max" else mins
                v = src[:, xcols[op].index(col)]
                out[out_name] = jnp.where(counts > 0, v, 0.0).astype(val_dtypes[col])
        return out, counts > 0
    return fn


# -- terminal lowering --------------------------------------------------------


def _lower_terminal(node: PH.PhysOp, ctx: ExecContext) -> tuple[str, Callable]:
    if isinstance(node, PH.MergeScalars):
        # per-LSM-component scalar programs (each with its own access path:
        # index-only count, fused range-count kernel, generic mask) merged
        # with +/max/min — the cross-component analogue of a psum. Pruned
        # runs were dropped by the planner: they never compile, gather, or
        # launch.
        subs = []
        for c in node.children:
            kind, build = _lower_terminal(c, ctx)
            assert kind == "scalar", f"MergeScalars over {kind} child"
            subs.append(build)
        merges = node.merges
        combine = {"sum": jnp.add, "max": jnp.maximum, "min": jnp.minimum}

        def fn(tables, params):
            outs = [s(tables, params) for s in subs]
            res = dict(outs[0])
            for o in outs[1:]:
                for name, op in merges:
                    res[name] = combine[op](res[name], o[name])
            return res
        return "scalar", fn

    if isinstance(node, PH.SubtractScalars):
        # anti-matter subtraction: visible = all matter − shadowed matter,
        # computed by two scalar programs over the same component.
        kind_a, minuend = _lower_terminal(node.children[0], ctx)
        kind_b, subtrahend = _lower_terminal(node.children[1], ctx)
        assert kind_a == kind_b == "scalar", (kind_a, kind_b)
        names = node.names

        def fn(tables, params):
            a = minuend(tables, params)
            b = subtrahend(tables, params)
            return {n: (a[n] - b[n]).astype(a[n].dtype)
                    if n in names and n in b else a[n] for n in a}
        return "scalar", fn

    if isinstance(node, PH.ShadowProbeCount):
        return "scalar", _lower_shadow_probe_count(node, ctx)

    if isinstance(node, PH.KernelRangeCount):
        return "scalar", _lower_kernel_range_count(node, ctx)

    if isinstance(node, PH.IndexOnlyCount):
        return "scalar", _lower_index_only_count(node, ctx)

    if isinstance(node, PH.MaskCount):
        child = _lower_stream(node.children[0], ctx)
        pred = node.predicate

        def fn(tables, params):
            env, mask = child(tables, params)
            if pred is not None:
                mask = mask & pred.evaluate(env, params)
            return {"count": ctx.strategy.count(mask)}
        return "scalar", fn

    if isinstance(node, PH.JoinCountOp):
        return "scalar", _lower_join_count(node, ctx)

    if isinstance(node, PH.ScalarAgg):
        child = _lower_stream(node.children[0], ctx)
        aggs = [(s.out_name, s.op, s.column) for s in node.aggs]

        def fn(tables, params):
            env, mask = child(tables, params)
            return {name: ctx.strategy.agg(env, mask, op, col)
                    for name, op, col in aggs}
        return "scalar", fn

    if isinstance(node, (PH.GroupAggGeneric, PH.KernelSegmentAgg)):
        return "grouped", _lower_groupagg(node, ctx)

    # table-producing terminals
    return "table", _lower_stream(node, ctx)


def _lower_kernel_range_count(node: PH.KernelRangeCount, ctx: ExecContext) -> Callable:
    """Lower onto the filter_count kernel: one (k, n) int32 tile of predicate
    columns + a (k, 2) runtime bounds operand. The column read bypasses the
    generic stream path so NO row mask is ever built outside the kernel —
    when the base table carries a ``__valid__`` padding column it folds in as
    one extra kernel row with bounds (1, 1). Newer components' anti-matter
    folds into the SAME row: the matter mask (valid ∧ not-shadowed) is the
    subtract-at-merge term, evaluated by the kernel itself. ``block_ids``
    (bind-time block zone-map survivors) drive the kernel grid: skipped
    tiles are never fetched."""
    key = f"{node.dataverse}.{node.dataset}"
    cols, los, his, has_valid = node.cols, node.los, node.his, node.has_valid
    shadow, key_col = node.shadow_sources, node.key_col
    block_ids = node.block_ids
    shard_blocks = None
    nsh, bp, rps = node.shard_layout()
    if block_ids is not None and nsh > 1:
        # multi-shard layout: expand the flat zone-block survivors into the
        # per-shard kernel-block matrix each shard scalar-prefetches.
        from repro.kernels.filter_count import BLOCK as _FC_BLOCK
        shard_blocks = ops.shard_block_arrays(block_ids, node.zone_block,
                                              _FC_BLOCK, nsh, bp, rps)
        block_ids = None

    def fn(tables, params):
        t = tables[key]
        rows = [t[c].astype(jnp.int32) for c in cols]
        lo_vals = [jnp.asarray(e.evaluate({}, params), jnp.int32) for e in los]
        hi_vals = [jnp.asarray(e.evaluate({}, params), jnp.int32) for e in his]
        if has_valid or shadow:
            n = rows[0].shape[0]
            matter = t["__valid__"] if has_valid \
                else jnp.ones((n,), jnp.bool_)
            if shadow:
                matter = matter & ~_shadowed(tables, t[key_col], shadow)
            rows.append(matter.astype(jnp.int32))
            lo_vals.append(jnp.int32(1))
            hi_vals.append(jnp.int32(1))
        mat = jnp.stack(rows)
        bounds = jnp.stack([jnp.stack(lo_vals), jnp.stack(hi_vals)], axis=1)
        cnt = ctx.strategy.kernel_filter_count(mat, bounds,
                                               block_ids=block_ids,
                                               shard_blocks=shard_blocks)
        return {"count": cnt.astype(jnp.int32)}
    return fn


def _lower_shadow_probe_count(node: PH.ShadowProbeCount, ctx: ExecContext) -> Callable:
    """The index-only subtrahend: the deduplicated union of the newer
    components' anti-key sets (a key may be tombstoned twice — a row must
    die exactly once), clipped to the predicate range, counts each
    tombstone's matter occurrences in this component's sorted primary index
    with two binary searches. The anti arrays are immutable for the life of
    the plan (the executable is stats-epoch keyed), so the sorted-unique
    union is computed ONCE here on the host and baked in as a constant —
    never re-sorted per query."""
    key = f"{node.dataverse}.{node.dataset}"
    ix_name = f"__ix_{node.index_col}__"
    anti_union = np.unique(np.concatenate(
        [np.asarray(ctx.catalog.get(dv, name).anti_keys_arr)
         for dv, name in node.shadow_sources]))

    def fn(tables, params):
        t = tables[key]
        ix_keys = t[ix_name]
        valid = t.get("__valid__", jnp.ones((ix_keys.shape[0],), jnp.bool_))
        anti = jnp.asarray(anti_union).astype(ix_keys.dtype)
        lo = node.lo.evaluate({}, params) if node.lo is not None else None
        hi = node.hi.evaluate({}, params) if node.hi is not None else None
        cnt = ctx.strategy.shadow_count(ix_keys, valid, anti, lo, hi)
        return {"count": cnt.astype(jnp.int32)}
    return fn


def _lower_index_only_count(node: PH.IndexOnlyCount, ctx: ExecContext) -> Callable:
    key = f"{node.dataverse}.{node.dataset}"

    def fn(tables, params):
        cols = tables[key]
        ix_keys = cols[f"__ix_{node.index_col}__"]
        valid = cols.get("__valid__",
                         jnp.ones((ix_keys.shape[0],), jnp.bool_))
        lo = node.lo.evaluate({}, params) if node.lo is not None else None
        hi = node.hi.evaluate({}, params) if node.hi is not None else None
        return {"count": ctx.strategy.index_count(ix_keys, valid, lo, hi)}
    return fn


def _lower_join_count(node: PH.JoinCountOp, ctx: ExecContext) -> Callable:
    lchild = _lower_stream(node.children[0], ctx)
    rchild = _lower_stream(node.children[1], ctx)
    left_on, right_on = node.left_on, node.right_on
    presorted = node.presorted
    if presorted:
        rkey_table = f"{node.presorted_key[0]}.{node.presorted_key[1]}"
        rkey_name = f"__ix_{right_on}__"

    join = ctx.strategy.kernel_join_count if node.kernel \
        else ctx.strategy.join_count

    def fn(tables, params):
        lenv, lm = lchild(tables, params)
        renv, rm = rchild(tables, params)
        rkey = tables[rkey_table][rkey_name] if presorted else renv[right_on]
        cnt = join(lenv[left_on], lm, rkey, rm, presorted)
        return {"count": cnt}
    return fn
