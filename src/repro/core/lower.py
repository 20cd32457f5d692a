"""Unified lowering: optimized UPIR ``Program`` -> JAX execution plan.

This is the single transformation the paper argues for: every frontend's program —
whatever model it was expressed in — arrives here as the same IR and leaves as the
same artifact. Two backends realize the plan:

  * **GSPMD backend** (default): the plan becomes ``NamedSharding`` in/out specs +
    donation + microbatch/remat/overlap parameters consumed by ``jax.jit``; XLA's
    SPMD partitioner materializes the collectives the IR prescribes.
  * **explicit backend**: the same plan drives ``shard_map`` with hand-placed
    ``jax.lax`` collectives (psum / all_gather / psum_scatter / all_to_all /
    ppermute), one per ``SyncOp``. Tests assert both backends are numerically
    identical — the JAX-level version of the paper's C2 claim.

The plan's sharding lookup is pytree-path based: symbols in the IR are
"params/blocks/wq"-style paths produced by ``path_str``.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from fnmatch import fnmatch
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import ir

# ----------------------------------------------------------------------- paths


def path_str(path) -> str:
    """Render a jax tree path as 'a/b/0/c'."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def tree_symbols(tree, prefix: str = "") -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Flatten a pytree of arrays/ShapeDtypeStructs into a UPIR symbol table."""
    out: Dict[str, Tuple[Tuple[int, ...], str]] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = (prefix + "/" if prefix else "") + path_str(path)
        out[name] = (tuple(leaf.shape), str(leaf.dtype))
    return out


# ----------------------------------------------------------------------- plan


@dataclasses.dataclass
class LoweredPlan:
    """Everything the numeric layer needs, extracted from the optimized IR."""

    program: ir.Program
    mesh_spec: ir.MeshSpec
    specs: Dict[str, P]                      # symbol -> PartitionSpec
    donated: Tuple[str, ...]                 # symbols whose buffers are donated
    host_offload: Tuple[str, ...]
    batch_axes: Tuple[str, ...]              # mesh axes the batch loop shards over
    seq_axis: Optional[str]                  # mesh axis for sequence parallelism
    microbatches: int                        # taskloop-derived accumulation count
    remat: str                               # none | selective | full
    grad_reduce: str                         # post | pipelined
    zero: bool                               # RS+AG decomposition present
    compression: Optional[str]               # None | int8
    collectives: Tuple[ir.SyncOp, ...]       # flattened sync schedule
    fingerprint: str = ""                    # canonical program fingerprint
    # paged-KV geometry (num_pages, page_size, pages_per_slot) when the
    # program manages the decode cache through paged_kv_alloc, else None
    page_geometry: Optional[Tuple[int, int, int]] = None
    # True when the paged cache is prefix-shared: the program carries
    # share/cow MemOps and the mm(shared_prefix) annotation, and the engine
    # runs ref-counted page aliasing with copy-on-write duplication
    prefix_sharing: bool = False
    # True when the decode cache's memory contract is fault-tolerant: the
    # program carries snapshot/restore MemOps and the mm(fault_tolerant)
    # annotation, and the engine runs quarantine + replay-exact recovery
    fault_tolerant: bool = False
    # True when the program is instrumented: it carries the mm(traced)
    # annotation and a trace_emit op, and the engine records host-side
    # request-lifecycle telemetry (runtime.telemetry)
    traced: bool = False
    # Host-pool page capacity when the paged cache is memory-tiered: the
    # program carries mm(tiered(N)) and device↔host kv_transfer MemOps, and
    # the engine spills cold refcount-1 prefix pages to a host pool instead
    # of dropping them. None for single-tier programs.
    tiering: Optional[int] = None
    # True when the pool topology is disaggregated prefill/decode: the
    # program carries mm(disaggregated) and prefill→decode kv_transfer
    # MemOps, and the engine prefills into a separate pool, handing KV off
    # at prefill completion
    disaggregated: bool = False
    # ModelFamily capability flags carried by the decode cache's data attr
    # (models.api.FamilySpec -> core.plans -> printer caps(...) rendering)
    capabilities: Tuple[str, ...] = ()
    # draft/target pairing (draft_arch_name, lookahead_k) when this is a
    # speculative verify plan (caps spec_verify/draft extensions), else None
    spec_decode: Optional[Tuple[str, int]] = None
    # admission-scheduling annotation carried by the decode cache's data attr
    # (runtime.scheduling -> core.plans -> printer sched(...) rendering), as
    # canonical sorted (key, value) pairs; None when the program declares no
    # policy (pre-scheduling programs keep their fingerprints)
    scheduling: Optional[Tuple[Tuple[str, Any], ...]] = None

    # ------------------------------------------------------------------ meshes

    def make_mesh(self, shape: Optional[Tuple[int, ...]] = None) -> Mesh:
        names = self.mesh_spec.names
        sizes = shape or tuple(s for _, s in self.mesh_spec.axes)
        return jax.make_mesh(
            sizes, names,
            axis_types=(jax.sharding.AxisType.Auto,) * len(names))

    # ---------------------------------------------------------------- shardings

    def spec(self, symbol: str) -> P:
        if symbol in self.specs:
            return self.specs[symbol]
        for pat, sp in self.specs.items():
            if fnmatch(symbol, pat):
                return sp
        return P()

    def sharding_tree(self, mesh: Mesh, tree, prefix: str = ""):
        def leaf_sharding(path, leaf):
            name = (prefix + "/" if prefix else "") + path_str(path)
            return NamedSharding(mesh, self.spec(name))
        return jax.tree_util.tree_map_with_path(leaf_sharding, tree)

    def batch_spec(self, extra_dims: int = 1) -> P:
        """PartitionSpec for a [batch, ...] input sharded over the batch axes."""
        return P(self.batch_axes if len(self.batch_axes) > 1 else
                 (self.batch_axes[0] if self.batch_axes else None),
                 *([None] * extra_dims))

    def donate_symbol(self, symbol: str) -> bool:
        return any(fnmatch(symbol, d) or symbol == d for d in self.donated)


# ------------------------------------------------------------------ IR -> plan


def partition_spec(attr: ir.DataAttr, ndim: Optional[int] = None) -> P:
    """Build a PartitionSpec from a DataAttr's distribution list."""
    if not attr.distribution:
        return P()
    max_dim = max(d.dim for d in attr.distribution)
    n = ndim if ndim is not None else max_dim + 1
    per_dim: list = [None] * n
    for d in attr.distribution:
        # "+"-joined axis names mean the dim is sharded over multiple mesh axes
        axes = tuple(d.axis.split("+")) if "+" in d.axis else d.axis
        if per_dim[d.dim] is None:
            per_dim[d.dim] = axes
        elif isinstance(per_dim[d.dim], tuple):
            per_dim[d.dim] = per_dim[d.dim] + (axes if isinstance(axes, tuple)
                                               else (axes,))
        else:
            per_dim[d.dim] = ((per_dim[d.dim],) +
                              (axes if isinstance(axes, tuple) else (axes,)))
    while per_dim and per_dim[-1] is None:
        per_dim.pop()
    return P(*per_dim)


def plan_from_program(prog: ir.Program) -> LoweredPlan:
    mesh_spec = None
    for n in ir.walk(prog):
        if isinstance(n, ir.SpmdRegion):
            mesh_spec = n.mesh
            break
    assert mesh_spec is not None, f"program {prog.name} has no SPMD region"

    symtab = prog.symbol_table()
    specs: Dict[str, P] = {}
    donated: list = []
    offload: list = []
    for attr in ir.find_all(prog, ir.DataAttr):
        shape, _ = symtab.get(attr.symbol, (None, None))
        ndim = len(shape) if shape is not None else None
        specs[attr.symbol] = partition_spec(attr, ndim)
        if ir.ext_get(attr.extensions, "donate", False):
            donated.append(attr.symbol)
        if ir.ext_get(attr.extensions, "host_offload", False):
            offload.append(attr.symbol)

    page_geometry = None
    prefix_sharing = False
    for attr in ir.find_all(prog, ir.DataAttr):
        if attr.allocator == "paged_kv_alloc":
            page_geometry = (ir.ext_get(attr.extensions, "num_pages", 0),
                             ir.ext_get(attr.extensions, "page_size", 0),
                             ir.ext_get(attr.extensions, "pages_per_slot", 0))
            prefix_sharing = bool(
                ir.ext_get(attr.extensions, "shared_prefix", False))
            break

    from .printer import CAP_EXT_KEYS, SCHED_EXT_KEYS
    capabilities: Tuple[str, ...] = ()
    spec_decode = None
    scheduling = None
    fault_tolerant = False
    traced = False
    tiering = None
    disaggregated = False
    for attr in ir.find_all(prog, ir.DataAttr):
        if attr.symbol == "cache":
            capabilities = tuple(k for k in CAP_EXT_KEYS
                                 if ir.ext_get(attr.extensions, k) is True)
            fault_tolerant = bool(
                ir.ext_get(attr.extensions, "fault_tolerant", False))
            traced = bool(ir.ext_get(attr.extensions, "traced", False))
            t = ir.ext_get(attr.extensions, "tiered")
            tiering = int(t) if t is not None else None
            disaggregated = bool(
                ir.ext_get(attr.extensions, "disaggregated", False))
            k = ir.ext_get(attr.extensions, "spec_verify")
            if k is not None:
                spec_decode = (str(ir.ext_get(attr.extensions, "draft", "")),
                               int(k))
            sched_pairs = tuple(
                (key, ir.ext_get(attr.extensions, key))
                for key in SCHED_EXT_KEYS
                if ir.ext_get(attr.extensions, key) is not None)
            if sched_pairs:
                scheduling = sched_pairs
            break

    batch_axes: list = []
    seq_axis = None
    microbatches = 1
    for loop in ir.find_all(prog, ir.LoopNode):
        for p in loop.parallel:
            if isinstance(p, ir.Worksharing) and p.axis:
                if loop.induction == "batch":
                    for a in p.axis.split("+"):
                        if a not in batch_axes:
                            batch_axes.append(a)
                if loop.induction in ("seq", "sequence"):
                    seq_axis = p.axis
            if isinstance(p, ir.Taskloop) and loop.induction in ("microbatch", "batch"):
                if p.num_tasks:
                    microbatches = max(microbatches, p.num_tasks)
                elif p.grainsize and isinstance(loop.upper, int):
                    microbatches = max(microbatches, loop.upper // max(p.grainsize, 1))

    syncs = tuple(s for s in ir.find_all(prog, ir.SyncOp))
    grad_reduce = "post"
    zero = False
    compression = None
    for s in syncs:
        if ir.ext_get(s.extensions, "schedule") == "pipelined":
            grad_reduce = "pipelined"
        if ir.ext_get(s.extensions, "zero_decomposed", False):
            zero = True
        c = ir.ext_get(s.extensions, "compression")
        if c:
            compression = c

    return LoweredPlan(
        program=prog, mesh_spec=mesh_spec, specs=specs, donated=tuple(donated),
        host_offload=tuple(offload), batch_axes=tuple(batch_axes), seq_axis=seq_axis,
        microbatches=microbatches,
        remat=ir.ext_get(prog.extensions, "remat", "none"),
        grad_reduce=grad_reduce, zero=zero, compression=compression,
        collectives=syncs, page_geometry=page_geometry,
        prefix_sharing=prefix_sharing, fault_tolerant=fault_tolerant,
        traced=traced, tiering=tiering, disaggregated=disaggregated,
        capabilities=capabilities, spec_decode=spec_decode,
        scheduling=scheduling)


# ----------------------------------------------------- explicit sync lowering


def lower_sync(sync: ir.SyncOp, value, axis_env: Optional[Tuple[str, ...]] = None):
    """Lower one SyncOp to its jax.lax collective (explicit/shard_map backend)."""
    axes = tuple(a for a in sync.axes if axis_env is None or a in axis_env)
    if not axes:
        return value
    if sync.name in ("allreduce", "reduction"):
        op = sync.operation or "add"
        fn = {"add": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin}[op]
        return jax.tree.map(lambda x: fn(x, axes), value)
    if sync.name == "reduce_scatter":
        return jax.tree.map(
            lambda x: jax.lax.psum_scatter(x, axes[0], scatter_dimension=0,
                                           tiled=True), value)
    if sync.name == "all_gather":
        return jax.tree.map(
            lambda x: jax.lax.all_gather(x, axes[0], axis=0, tiled=True), value)
    if sync.name == "all_to_all":
        return jax.tree.map(
            lambda x: jax.lax.all_to_all(x, axes[0], split_axis=0, concat_axis=1,
                                         tiled=True), value)
    if sync.name == "broadcast":
        # broadcast from primary unit: implemented as select + psum
        def bcast(x):
            idx = jax.lax.axis_index(axes[0])
            src = int(sync.primary.split(":")[1]) if ":" in sync.primary and \
                sync.primary.split(":")[1] != "*" else 0
            return jax.lax.psum(jax.numpy.where(idx == src, x, 0), axes[0])
        return jax.tree.map(bcast, value)
    if sync.name in ("shift", "send", "recv"):
        def shift(x):
            n = jax.lax.axis_size(axes[0])
            perm = [(i, (i + 1) % n) for i in range(n)]
            return jax.lax.ppermute(x, axes[0], perm)
        return jax.tree.map(shift, value)
    if sync.name == "barrier":
        return value  # SPMD programs on TPU are bulk-synchronous per-op already
    raise NotImplementedError(
        f"sync '{sync.name}' has no TPU lowering (see DESIGN.md §2 degenerations)")


class UnsupportedOnTarget(NotImplementedError):
    pass


# ------------------------------------------------------------------ plan cache


class PlanCache:
    """Process-wide cache of compiled serving artifacts.

    Entries are keyed by a canonical ``Program`` fingerprint
    (``printer.program_fingerprint``) plus whatever distinguishes the compiled
    artifact — backend, mesh shape, batch geometry — so a repeat request for the
    same (config, shape, backend, mesh) skips the pass pipeline, the
    IR -> plan extraction, AND the jax.jit re-trace. LRU-bounded; hit/miss
    counters feed the serving engine's stats.
    """

    def __init__(self, maxsize: int = 128):
        self.maxsize = maxsize
        self.hits = 0
        self.misses = 0
        self._entries: "OrderedDict[Any, Any]" = OrderedDict()
        self._lock = threading.Lock()

    def get_or_build(self, key, build: Callable[[], Any]):
        """Return the cached value for ``key``, building (and caching) on miss."""
        with self._lock:
            if key in self._entries:
                self.hits += 1
                self._entries.move_to_end(key)
                return self._entries[key]
            self.misses += 1
        value = build()
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
        return value

    def lowered_plan(self, prog: ir.Program, *, backend: str = "jit",
                     mesh_shape: Optional[Tuple[Tuple[str, int], ...]] = None,
                     trace: Optional[list] = None) -> LoweredPlan:
        """Optimized-IR + LoweredPlan for ``prog``, cached by fingerprint.

        On a hit the unified pass pipeline does not run at all; ``trace`` (the
        pass-trace list) only grows on misses, which is itself a visible
        witness of cache effectiveness.
        """
        from .passes import run_pipeline
        from .printer import program_fingerprint
        fp = program_fingerprint(prog)

        def build() -> LoweredPlan:
            plan = plan_from_program(run_pipeline(prog, trace=trace))
            plan.fingerprint = fp
            return plan

        return self.get_or_build(("plan", fp, backend, mesh_shape), build)

    def stats(self) -> Dict[str, Any]:
        total = self.hits + self.misses
        return {"hits": self.hits, "misses": self.misses,
                "size": len(self._entries),
                "hit_rate": self.hits / total if total else 0.0}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = 0
            self.misses = 0


_PLAN_CACHE = PlanCache()


def default_plan_cache() -> PlanCache:
    """The process-wide PlanCache shared by server/engine entry points."""
    return _PLAN_CACHE
