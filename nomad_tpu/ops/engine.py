"""Host↔device placement engine.

Bridges the control plane (snapshots, Job/TaskGroup objects, reconciler
output) and the device kernels: packs state, pads to shape buckets to bound
recompilation, runs the `place` kernel, and maps node rows back to ids +
AllocMetric.  This is the seam the Go worker would call through the PJRT
bridge (SURVEY.md §7 P6); in-process it is plain Python.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nomad_tpu.pack.interner import UNSET
from nomad_tpu.pack.packer import (ClusterPacker, JobContext, NodeTensors,
                                   TGTensors, tg_static_ports)
from nomad_tpu.pack.spread import (
    SpreadTensors,
    job_spreads,
    lower_spreads,
    spread_job_rows,
    spread_landscape,
    spread_signature,
)
from nomad_tpu.structs import (
    AllocMetric,
    Job,
    NodeScoreMeta,
    RES_DIMS,
    RES_NAMES,
    RowMetrics,
    SCHED_ALGO_SPREAD,
    TaskGroup,
)

from .feasibility import (constraint_mask, feasible_mask_jit,
                          place_system_jit)
from .preempt import Preemptor, preemption_enabled
from .scan_fused import scan_gate
from .select import (
    FILL_K, MultiEvalInputs, PlacementInputs, SCAN_PACKED_FIELDS, TOP_K,
    pack_scan_inputs, place_multi_chained_jit,
    place_multi_compact_chained_jit, place_multi_compact_packed_jit,
    place_multi_packed_jit, place_packed_jit)

# Minimum homogeneous batch size before a solo eval's water-fill rounds
# (the flat multi-eval kernel, as a wave of one item) beat the
# per-placement scan (scan is exact sequential semantics; a water-fill
# commits whole rounds between state refreshes).
BULK_THRESHOLD = 64

# Static ports as a feasibility rule (ISSUE 38): the single-device scan
# and the flat multi-eval kernel carry, per static port value a launch's
# groups ask, the nodes that hold it (select.port_state), so a static
# ask passes by a node whose port is taken, by a live allocation, by a
# wave-mate or by its own job earlier in the launch, as upstream's
# BinPackIterator does.  What benchmark/configs/ports50k.py asks the
# program for by name.
STATIC_PORT_FEASIBILITY = True
# the fewest slots of a launch's port state ([Kp, N], Kp on the
# power-of-two ladder from here): a wave of services asks a handful of
# well-known values, and one bucket for one to four of them keeps a
# drain's waves on one compiled shape
PORT_SLOTS_MIN = 4
# the most rows a chain of waves hands on for static values its latest
# launch did not ask; past it the oldest are dropped (their next ask reads
# the state again, and the applier's re-check holds what was in flight)
PORT_ROWS_CARRIED = 64

# fixed-size chunks so the delta-replay scatter compiles ONCE, not once
# per power-of-two delta size (a 100k-alloc plan's replay was paying a
# multi-second device compile the first time each size appeared)
SCATTER_CHUNK = 16384
_scatter_add_jit = jax.jit(lambda u, r, v: u.at[r].add(v))


def _fold_rows(npad: int) -> int:
    """The rows of usage replay a single-device scan launch carries in
    its input buffer (`select.pack_scan_inputs`): fixed for a fleet, so
    the program keeps one shape; room for a replay that touches every
    node twice (a plan's placements and the stops before it), within the
    scatter ladder's bounds.  More goes by `_used_device`'s scatter."""
    return min(SCATTER_CHUNK, _pad_pow2(2 * npad, lo=512))


# the single-device wave programs by `dispatch_batch`'s launch kind (their
# node-sharded twins: SHARDED_KINDS below)
_WAVE_JITS = {"multi": place_multi_packed_jit,
              "multi_chained": place_multi_chained_jit,
              "multi_compact": place_multi_compact_packed_jit,
              "multi_compact_chained": place_multi_compact_chained_jit}


# Process-wide mesh + sharded-kernel caches.  Critically NOT per-engine:
# every Server builds its own PlacementEngine, and a fresh jit closure per
# engine would recompile the sharded kernels (seconds to tens of seconds
# each) on every server start.  Keyed by the mesh's device ids so two
# equivalent meshes share compilations.
_MESH_SINGLETON = None
_SHARDED_FN_CACHE: Dict[tuple, object] = {}

# shape buckets already launched at least once in this process — the
# compile-ledger mirror of jax's process-global jit caches (a second
# engine in the same process hits the jit cache, so it must not count
# a fresh compile).  See `PlacementEngine._launch`.
_KERNEL_SHAPES_SEEN: set = set()


def _compile_ledger():
    """Process compile ledger (core/profiling.py), imported lazily for
    the same first-importer-order reason as `_registry` below."""
    from nomad_tpu.core.profiling import COMPILE
    return COMPILE


def _registry():
    """Process metrics registry, imported lazily: `nomad_tpu.core`'s
    package __init__ imports the worker, which imports this package — a
    module-level import here would make the first-importer order
    matter."""
    from nomad_tpu.core.telemetry import REGISTRY
    return REGISTRY


def _default_mesh():
    global _MESH_SINGLETON
    if _MESH_SINGLETON is None:
        from nomad_tpu.parallel.mesh import make_mesh
        _MESH_SINGLETON = make_mesh()
    return _MESH_SINGLETON


# Every program the node-sharded path launches, by the engine's launch
# kind: (its builder in parallel/mesh.py, the builder's keyword
# arguments).  The chained kinds are the donated-chain variants: wave
# k+1 consumes wave k's dead sharded usage buffer in place.  On an engine
# with a mesh each launch of one of these counts in
# `nomad.engine.mesh_launches{kind}` (`PlacementEngine._launch`).
SHARDED_KINDS = {
    "scan": ("place_sharded_packed_fn", {}),
    "multi": ("place_multi_sharded_packed_fn", {}),
    "multi_chained": ("place_multi_sharded_packed_fn", {"chained": True}),
    "multi_compact": ("place_multi_compact_sharded_fn", {}),
    "multi_compact_chained": ("place_multi_compact_sharded_fn",
                              {"chained": True}),
    "scatter": ("scatter_add_sharded_fn", {}),
}


def _sharded_fn(mesh, kind: str, *shape_args):
    key = (kind, tuple(d.id for d in mesh.devices.flat)) + shape_args
    fn = _SHARDED_FN_CACHE.get(key)
    if fn is None:
        from nomad_tpu.parallel import mesh as pmesh
        builder, kwargs = SHARDED_KINDS[kind]
        fn = getattr(pmesh, builder)(mesh, *shape_args, **kwargs)
        _SHARDED_FN_CACHE[key] = fn
    return fn


def mesh_launches_by_program() -> Dict[str, int]:
    """{program name as a trace shows it less its `jit_`: launches} of
    the node-sharded programs this process has launched, from
    `nomad.engine.mesh_launches{kind}` and the programs built."""
    names = {key[0]: fn.__name__ for key, fn in _SHARDED_FN_CACHE.items()}
    return {names[label.partition("=")[2]]: int(n) for label, n in
            _registry().counter_labels("nomad.engine.mesh_launches").items()}


def _pad_rows(a: np.ndarray, n_pad: int, fill=0) -> np.ndarray:
    """Pad a host array's leading (node) axis to n_pad rows."""
    n = a.shape[0]
    if n == n_pad:
        return a
    out = np.full((n_pad,) + a.shape[1:], fill, a.dtype)
    out[:n] = a
    return out


def _pad_cols(a: np.ndarray, n_pad: int, fill=0) -> np.ndarray:
    """Pad a host array's trailing (node) axis to n_pad columns."""
    n = a.shape[-1]
    if n == n_pad:
        return a
    out = np.full(a.shape[:-1] + (n_pad,), fill, a.dtype)
    out[..., :n] = a
    return out


@dataclass
class PlacementRequest:
    """One placement the reconciler asked for."""
    tg_name: str
    prev_node_id: str = ""       # reschedule penalty target


@dataclass
class BatchItem:
    """One eval's placement block inside a multi-eval batch: `count`
    fresh placements of `tg` for `job` (the batch-eligible shape the
    worker's batched path prepares — reconcile produced exactly one
    PlaceBlock and nothing else)."""
    job: Job
    tg: TaskGroup
    count: int


@dataclass
class PlacementDecision:
    tg_name: str
    node_id: Optional[str]       # None = no feasible node
    score: float
    metric: AllocMetric
    # allocs to evict to make this placement possible (preemption)
    evictions: List = field(default_factory=list)


def port_collision_dimension(asks) -> str:
    """The exhausted dimension of a node lost to static-port state, as
    upstream's NetworkIndex names it; with the value where the launch's
    groups ask one value between them."""
    values = {v for a in asks for v in a}
    return "network: reserved port collision" + (
        f" {next(iter(values))}" if len(values) == 1 else "")


def _pad_pow2(x: int, lo: int = 8) -> int:
    p = lo
    while p < x:
        p *= 2
    return p


def _port_slots(asks, g_pad: int):
    """(the static port values a launch's groups ask, in slot order;
    `[g_pad, Kp]` bool, the slots each group asks), Kp on the
    power-of-two ladder from PORT_SLOTS_MIN."""
    asked = tuple(sorted({v for a in asks for v in a}))
    slot = {v: k for k, v in enumerate(asked)}
    pt_ask = np.zeros((g_pad, _pad_pow2(len(asked), lo=PORT_SLOTS_MIN)), bool)
    for g, a in enumerate(asks):
        for v in a:
            pt_ask[g, slot[v]] = True
    return asked, pt_ask


# lane-parallel scheduling cap: lanes beyond this stop paying (each step's
# [L, N] math grows linearly while the sequential depth shrinks as 1/L)
MAX_LANES = 8


def _sig_disjoint(con_a, con_b, luts) -> bool:
    """Prove two lowered constraint signatures select DISJOINT node sets,
    from structure alone (conservative: False = "could not prove", not
    "overlaps").  Sufficient conditions, per shared column:
      EQ(v1) vs EQ(v2), v1 != v2            — an attr has one value
      EQ(v)  vs LUT(row) with not row[v]    — v outside the LUT set
      LUT(a) vs LUT(b) with (a & b) empty   — e.g. two CSI topologies
                                              over disjoint node-id sets
    `luts` is the packer's host LUT matrix [L, V] bool."""
    from nomad_tpu.pack.packer import DOP_EQ, DOP_LUT
    by_col: Dict[int, list] = {}
    for col, op, arg in con_a:
        if op in (DOP_EQ, DOP_LUT):
            by_col.setdefault(int(col), []).append((int(op), int(arg)))
    nrows, v = luts.shape
    for col, op, arg in con_b:
        op, arg = int(op), int(arg)
        if op not in (DOP_EQ, DOP_LUT):
            continue
        for op_a, arg_a in by_col.get(int(col), ()):
            if op_a == DOP_EQ and op == DOP_EQ:
                if arg_a != arg:
                    return True
            elif op_a == DOP_EQ and op == DOP_LUT:
                if arg < nrows and (arg_a >= v or not luts[arg, arg_a]):
                    return True
            elif op_a == DOP_LUT and op == DOP_EQ:
                if arg_a < nrows and (arg >= v or not luts[arg_a, arg]):
                    return True
            else:
                if (arg_a < nrows and arg < nrows
                        and not (luts[arg_a] & luts[arg]).any()):
                    return True
    return False


def _host_signature_masks(attrs, elig, base_by_sig, con_by_sig, luts):
    """Per-signature static feasibility masks, evaluated on the host in
    numpy with the SAME constraint_mask body the device kernels trace
    (no semantic drift, and no second JAX backend: the process may see
    the accelerator only).  Returns [U, n] bool numpy."""
    cm = constraint_mask(attrs, np.stack(con_by_sig), luts, xp=np)
    return cm & elig[None, :] & np.stack(base_by_sig)


def _disjoint_cliques(sig_rows, luts, weights):
    """Greedy partition of signature indices into cliques of pairwise
    provably-disjoint signatures (heaviest-first so the biggest lanes
    land together).  Each clique's members run as concurrent lanes; the
    cliques themselves run sequentially."""
    u = len(sig_rows)
    order = sorted(range(u), key=lambda s: -weights[s])
    memo: Dict[tuple, bool] = {}

    def dis(a: int, b: int) -> bool:
        key = (a, b) if a < b else (b, a)
        hit = memo.get(key)
        if hit is None:
            hit = _sig_disjoint(sig_rows[a], sig_rows[b], luts)
            memo[key] = hit
        return hit

    assigned = [False] * u
    cliques = []
    for s in order:
        if assigned[s]:
            continue
        clique = [s]
        assigned[s] = True
        for t in order:
            if assigned[t] or len(clique) >= MAX_LANES:
                continue
            if all(dis(t, m) for m in clique):
                clique.append(t)
                assigned[t] = True
        cliques.append(clique)
    return cliques


def _resolve_compact_fills(buf_np: np.ndarray, fills_full, slot_k: int):
    """The compact laned kernel's overflow protocol: the small buffer's
    fill prefix is complete iff the per-round prefix counts sum to the
    placed-total meta column; otherwise fetch the device-resident full
    fills and rebuild the full-layout buffer.  Returns (buf, slot_k)
    where slot_k == 0 means full layout."""
    if not slot_k:
        return buf_np, 0
    cnt_small = buf_np[:, :slot_k] & 2047
    if np.array_equal(cnt_small.sum(axis=1), buf_np[:, slot_k + 12]):
        return buf_np, slot_k
    full = fills_full() if callable(fills_full) else np.asarray(fills_full)
    return np.concatenate([full, buf_np[:, slot_k:]], axis=1), 0


def _unpack_rounds(buf: np.ndarray, round_size: int, p_real: int,
                   slot_k: int = 0):
    """Expand the water-fill kernels' per-round buffer (see
    select.pack_round_buffer for the layout) into per-placement picks
    plus the per-round metric block.  Placements within a round are
    interchangeable, so per-node fill counts expand with np.repeat.

    `slot_k`: fill slots per buffer row when they differ from the round
    size (the compact-output kernel emits a FILL_K-slot prefix while
    rounds still hold `round_size` placements)."""
    n_rounds = buf.shape[0]
    slot_k = slot_k or round_size
    fills = buf[:, :slot_k]
    meta = buf[:, slot_k:]
    rows_r = fills >> 11
    cnt_r = fills & 2047
    placed_r = meta[:, 12]

    picks = np.full(n_rounds * round_size, -1, np.int32)
    for r in range(n_rounds):
        lo = r * round_size
        k = int(placed_r[r])
        if k <= 0:
            continue
        nz = cnt_r[r].nonzero()[0]
        picks[lo:lo + k] = np.repeat(rows_r[r, nz], cnt_r[r, nz])[:k]
    return picks[:p_real], meta


# the meta block's per-dimension exhaustion columns, in RES_NAMES' order
# (select.pack_round_buffer: three before `placed` at 12, the rest after)
_META_DIM_EX = [9, 10, 11] + list(range(13, 10 + RES_DIMS))
# the column after them: nodes a round lost to static-port state (the
# flat multi-eval kernel on a wave that carries it; zero elsewhere)
_META_PORT_EX = 10 + RES_DIMS


def _stop_delta(t: NodeTensors, npad: int, given_back):
    """A plan's stopped allocations on `t`'s rows.  `given_back` is
    {node id: (the usage they give back, how many of them are the
    job's)}; returns the delta to add to `used` ([npad, RES_DIMS],
    negative) and the job's count given back a node ([n]).  By node id,
    not row: a launch reads the node table again, and rows move when it
    is rebuilt."""
    delta = np.zeros((npad, RES_DIMS), np.int32)
    jc_back = np.zeros(t.n, np.int32)
    for nid, (usage, own) in given_back.items():
        row = t.id_to_row.get(nid)
        if row is not None:
            delta[row] -= usage
            jc_back[row] += own
    return delta, jc_back



@dataclass
class BulkDecisions:
    """Array-form result of a homogeneous placement batch: one shared
    AllocMetric per water-fill round instead of per-placement objects.
    Building 100k PlacementDecision + AllocMetric objects cost more than
    the device work; the scheduler materializes allocs straight from
    `picks`.  The exact scan answers a block of fresh placements in the
    same form, its per-placement metrics as columns (`rows`)."""
    tg_name: str
    picks: np.ndarray                  # [P] node row or -1
    node_ids: List[str]                # row -> node id (shared, read-only)
    round_size: int
    metrics: List[AllocMetric]         # one per round, shared by the round
    evictions: Dict[int, List] = field(default_factory=dict)
    nodes_evaluated: int = 0
    # the exact scan's form (a block of fresh placements that could not
    # ride the water-fill): `round_size` 1, `metrics` empty, and one
    # metric a PLACEMENT kept as the columns the scan returned
    rows: Optional[RowMetrics] = None
    scores: Optional[np.ndarray] = None     # [P] float32, the picks' own

    def metric_at(self, i: int) -> AllocMetric:
        """Placement i's metric: its own, built here, or its round's."""
        if self.rows is not None:
            return self.rows.metric(i)
        return self.metrics[min(i // self.round_size,
                                len(self.metrics) - 1)]


class PlacementEngine:
    """Owns a ClusterPacker + device caches for one scheduling session.

    Multi-device: when the runtime exposes more than one device (a real
    TPU slice, or the virtual CPU mesh in tests), the engine AUTOMATICALLY
    shards the node axis over a `jax.sharding.Mesh` and routes every
    kernel launch through the parallel/mesh sharded variants (two-stage
    top-k over ICI) — SURVEY §6.7/§7 P7.  Node tensors are padded to a
    multiple of the mesh size (padded rows are ineligible) and cached
    device-side with NamedSharding."""

    def __init__(self, packer: Optional[ClusterPacker] = None,
                 mesh=None) -> None:
        """`mesh`: None = auto (shard when >1 device), False = force
        single-device, or an explicit jax.sharding.Mesh."""
        self.packer = packer or ClusterPacker()
        if mesh is None and jax.device_count() > 1:
            mesh = _default_mesh()
        self.mesh = mesh = mesh or None
        self._ndev = 1 if mesh is None else mesh.devices.size
        self._node_sharding = None
        self._scatter_fn = _scatter_add_jit
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._node_sharding = NamedSharding(mesh, PartitionSpec("nodes"))
            self._scatter_fn = _sharded_fn(mesh, "scatter")
        self._dev_cache: Dict[str, object] = {}
        self._cache_version: Tuple[int, int, int] = (-1, -1, -1)
        self._cache_npad: int = -1
        self._used_version: int = -1
        self._used_dev = None
        # running meters for the mesh deployment (bench.py surfaces them
        # per wave): bytes re-uploaded via dirty-SHARD patches (vs full
        # tensor re-syncs) and the per-launch cross-shard collective
        # payload (the two-stage top-k all_gathers — O(k·n_devices) per
        # round by construction, never O(n_nodes))
        self.shard_h2d_bytes: int = 0
        self.collective_bytes: int = 0
        self._const_cache: Dict[tuple, object] = {}
        # compact-lane candidate frames (_candidate_frames), by what they
        # were derived from; a handful, oldest out
        self._frame_cache: Dict[tuple, tuple] = {}
        # device requests' static masks (device_static_mask), by node
        # table version and request signature; a handful, oldest out
        self._device_mask_cache: Dict[tuple, np.ndarray] = {}
        # spread stanzas' value landscapes (_spread_landscapes), by node
        # table version and spread signature; a handful, oldest out
        self._spread_cache: Dict[tuple, tuple] = {}
        # static port values' holder masks (static_port_mask), by value:
        # (node table version, padded n, the value's holder version, the
        # [npad] bool on the device); a few dozen, oldest out
        self._port_mask_cache: Dict[int, tuple] = {}
        self._single_group: Optional[Tuple[int, bool]] = None
        self._dc_cache: Optional[Tuple[int, Dict[str, int]]] = None
        # host->device sync meter (ops/executor.py installs it): called
        # with (bytes, seconds, cause) for every node-state upload —
        # full node tensors ("initial-upload"), dirty-shard patches
        # ("dirty-shard-patch"), and the per-eval delta-replay scatters
        # ("invalidation-replay"); the d2h twin meters result fetches
        self.h2d_observer = None
        self.d2h_observer = None
        # optional core/wavepipe.StageTimers (wired by the Server): each
        # `place` call records one "solo_place" interval
        self.timers = None
        # say which backend JAX chose: a failed accelerator init would
        # otherwise serve from the CPU with nothing in the logs
        from nomad_tpu.core.logging import log
        dev0 = jax.devices()[0]
        log("engine", "info", "placement engine up",
            platform=dev0.platform, device_kind=dev0.device_kind,
            n_devices=jax.device_count(), mesh_devices=self._ndev)

    def _note_h2d(self, nbytes: int, seconds: float,
                  cause: str = "initial-upload") -> None:
        obs = self.h2d_observer
        if obs is not None and nbytes:
            obs(nbytes, seconds, cause)

    def _note_d2h(self, nbytes: int, seconds: float,
                  cause: str = "result-fetch") -> None:
        obs = self.d2h_observer
        if obs is not None and nbytes:
            obs(nbytes, seconds, cause)

    def _fetch(self, arr) -> np.ndarray:
        """Materialize a device result buffer on the host with the d2h
        ledger fed ("result-fetch" cause): every byte the scheduler
        pulls back from the chip is attributed, matching the h2d side."""
        t0 = time.perf_counter_ns()
        out = np.asarray(arr)
        self._note_d2h(out.nbytes, (time.perf_counter_ns() - t0) / 1e9)
        return out

    def _launch(self, kind: str, shape_key: tuple, fn, *args):
        """Run one compiled-kernel launch under the compile ledger
        (core/profiling.py): the FIRST launch of a shape bucket pays
        trace+lower+compile synchronously inside the call (PERF.md §13
        measured this split by hand), later launches are steady
        dispatches.  The bucket key mirrors what makes jax recompile —
        kernel kind + the static shape arguments."""
        site = f"engine.{kind}/" + "x".join(str(s) for s in shape_key)
        key = (kind, shape_key)
        if self.mesh is not None and kind in SHARDED_KINDS:
            _registry().inc("nomad.engine.mesh_launches", kind=kind)
        t0 = time.perf_counter()
        out = fn(*args)
        dt = time.perf_counter() - t0
        led = _compile_ledger()
        if key in _KERNEL_SHAPES_SEEN:
            led.note_hit(site)
            led.note_steady(site, dt)
        else:
            _KERNEL_SHAPES_SEEN.add(key)
            led.note_miss(site, dt)
        return out

    def device_resident_bytes(self) -> int:
        """Estimated HBM residency of this engine's retained device
        buffers (node tensors, resident `used`, const cache, candidate
        frames).  Reads WITHOUT the packer lock — callers sit inside
        _note_h2d, some of whose call sites already hold it — so a
        concurrent eviction can tear an iteration; this is a gauge, skip
        and report the partial sum rather than block the hot path."""
        total = 0
        try:
            for v in tuple(self._dev_cache.values()):
                total += int(getattr(v, "nbytes", 0))
            u = self._used_dev
            if u is not None:
                total += int(getattr(u, "nbytes", 0))
            for v in tuple(self._const_cache.values()):
                total += int(getattr(v, "nbytes", 0))
            for _, _, frames_dev in tuple(self._frame_cache.values()):
                total += sum(int(a.nbytes) for a in frames_dev)
        except RuntimeError:
            pass
        return total

    @property
    def n_devices(self) -> int:
        return self._ndev

    def padded_row_fraction(self, n: int) -> float:
        """Fraction of kernel rows that are mesh padding (ineligible)."""
        npad = self._padded_n(max(n, 1))
        return (npad - n) / npad if npad else 0.0

    def _note_collective(self, rounds: int, kk: int,
                         width: int = 5, extra: int = 64) -> int:
        """Meter one mesh launch's analytic cross-shard collective
        payload (bytes RECEIVED per device): each round's two-stage
        top-k all_gathers a [width, kk] candidate pack from every shard
        — kk <= round_size, so the per-round payload is O(top-k ·
        n_devices) and INDEPENDENT of n_nodes — plus ~`extra` bytes of
        psum'd round metrics.  Exposed as engine.collective_bytes and
        the nomad.engine.collective_bytes counter (bench.py reports it
        per wave)."""
        nbytes = rounds * (width * kk * 4 * self._ndev + extra)
        self.collective_bytes += nbytes
        _registry().inc("nomad.engine.collective_bytes", nbytes)
        return nbytes

    def _padded_n(self, n: int) -> int:
        """Node count padded to a mesh multiple (identity single-device)."""
        return ((n + self._ndev - 1) // self._ndev) * self._ndev

    def _sharded(self, kind: str, *shape_args):
        return _sharded_fn(self.mesh, kind, *shape_args)

    # ------------------------------------------------------------ devices

    def _node_arrays(self, t: NodeTensors):
        """Upload node tensors once per (version, vocab, width) — the
        incremental HBM sync point.  Width matters: ensure_column can widen
        attrs after a build without bumping the row version.  On a mesh the
        node axis is padded to a device multiple (padded rows ineligible)
        and placed with NamedSharding.

        Mesh incremental sync: when the version bump came from dirty-ROW
        refreshes (packer.node_rows_dirty_since — eligibility/attribute
        writes, row mapping unchanged) and the attrs width and padding
        are stable, only the SHARDS holding dirty rows re-upload; clean
        shards keep their resident device buffers
        (jax.make_array_from_single_device_arrays).  A 1M-node table on
        8 devices then pays 1/8th of the full sync for a single node
        write instead of re-uploading every tensor."""
        key = (t.version, len(self.packer.interner), t.attrs.shape[1])
        if self._cache_version != key:
            t0h = time.perf_counter()
            # packer.lock: a concurrent update()/_on_allocs in another
            # thread mutates these arrays in place — copying mid-mutation
            # would cache a torn tensor under a version that claims
            # consistency.  jnp.array (copy=True): on the CPU backend
            # jnp.asarray zero-copies the numpy buffer, and the packer
            # mutates it after the copy too.
            with self.packer.lock:
                npad = self._padded_n(t.n)
                h2d = 0
                patched = False
                if (self.mesh is not None and self._dev_cache
                        and self._cache_version[2] == key[2]
                        and self._cache_npad == npad):
                    rows = self.packer.node_rows_dirty_since(
                        self._cache_version[0])
                    if rows is not None:
                        h2d = self._patch_node_shards(t, npad, rows)
                        patched = True
                if not patched:
                    if self.mesh is None:
                        self._dev_cache = {
                            "attrs": jnp.array(t.attrs),
                            "cap": jnp.array(t.cap),
                            "elig": jnp.array(t.elig),
                        }
                    else:
                        put = partial(jax.device_put,
                                      device=self._node_sharding)
                        self._dev_cache = {
                            "attrs": put(_pad_rows(t.attrs, npad, UNSET)),
                            "cap": put(_pad_rows(t.cap, npad)),
                            "elig": put(_pad_rows(t.elig, npad, False)),
                        }
                    h2d = sum(int(getattr(v, "nbytes", 0))
                              for v in self._dev_cache.values())
                    # a full re-upload invalidates the resident `used`
                    # copy too (row remap / width change); a shard patch
                    # keeps it — _used_device heals the dirty shards
                    self._used_version = -1
                    self._used_dev = None
                self._cache_version = key
                self._cache_npad = npad
            self._note_h2d(h2d, time.perf_counter() - t0h,
                           "dirty-shard-patch" if patched
                           else "initial-upload")
        return self._dev_cache

    def _shard_of(self, rows: np.ndarray, npad: int) -> set:
        """Mesh shard indices owning `rows` (node axis split evenly)."""
        nloc = max(npad // self._ndev, 1)
        return set((np.asarray(rows, np.int64) // nloc).tolist())

    def _patch_shards(self, arr, host: np.ndarray, fill, npad: int,
                      dirty_shards: set) -> Tuple[object, int]:
        """Reassemble a node-sharded device array with only
        `dirty_shards` re-uploaded from the host tensor (remaining
        shards reuse their resident per-device buffers).  Returns
        (new array, bytes uploaded)."""
        nloc = max(npad // self._ndev, 1)
        shape = (npad,) + host.shape[1:]
        sharding = arr.sharding
        old = {s.device: s.data for s in arr.addressable_shards}
        bufs = []
        nbytes = 0
        for dev, idx in sharding.addressable_devices_indices_map(
                shape).items():
            lo = idx[0].start or 0
            if lo // nloc in dirty_shards:
                sl = np.full((nloc,) + host.shape[1:], fill, host.dtype)
                real = max(min(lo + nloc, host.shape[0]) - lo, 0)
                if real:
                    sl[:real] = host[lo:lo + real]
                buf = jax.device_put(sl, dev)
                nbytes += sl.nbytes
            else:
                buf = old[dev]
            bufs.append(buf)
        out = jax.make_array_from_single_device_arrays(
            shape, sharding, bufs)
        return out, nbytes

    def _patch_node_shards(self, t: NodeTensors, npad: int,
                           rows: np.ndarray) -> int:
        """Dirty-shard re-upload of attrs/cap/elig (packer lock held by
        the caller).  Zero rows = nothing to move (version-only bump)."""
        if rows.size == 0:
            return 0
        dirty = self._shard_of(rows, npad)
        nbytes = 0
        cache = dict(self._dev_cache)
        for name, host, fill in (("attrs", t.attrs, UNSET),
                                 ("cap", t.cap, 0),
                                 ("elig", t.elig, False)):
            cache[name], nb = self._patch_shards(
                cache[name], host, fill, npad, dirty)
            nbytes += nb
        self._dev_cache = cache
        self.shard_h2d_bytes += nbytes
        _registry().inc("nomad.engine.shard_h2d_bytes", nbytes)
        return nbytes

    def _used_device(self, t: NodeTensors):
        """Device-resident usage tensor.  Plan applies dirty `used` every
        eval; rather than re-upload [N,3] per eval, the packer's delta
        log is replayed as an on-device scatter-add (upload size
        O(changed rows), not O(N))."""
        # The whole read-version → fetch-deltas → commit sequence holds the
        # packer lock: the applier thread appends deltas and bumps
        # t.used_version concurrently, and an unlocked interleave can
        # record a version whose delta was never applied (ghost capacity)
        # or apply one twice.  The lock also keeps the full t.used copy
        # from reading a torn mid-scatter tensor.
        with self.packer.lock:
            ver = t.used_version
            if self._used_dev is not None and self._used_version == ver:
                return self._used_dev
            t0h = time.perf_counter()
            h2d_bytes = 0
            deltas = None
            if self._used_dev is not None:
                deltas = self.packer.used_deltas_since(self._used_version)
            if deltas is None and self._used_dev is not None \
                    and self.mesh is not None:
                # a dirty-ROW refresh sentinel intervened (node write):
                # heal only the shards whose rows may be stale — the
                # union of real-delta rows and sentinel-refreshed rows —
                # from the host tensor, keeping clean shards resident
                # (the tentpole's "invalidation re-uploads only dirty
                # shards"; a full rebuild still returns None here and
                # falls through to the full upload)
                sync_rows = self.packer.used_sync_rows_since(
                    self._used_version)
                if sync_rows is not None \
                        and self._cache_npad == self._padded_n(t.n):
                    if sync_rows.size:
                        # no host copy of the full tensor: _patch_shards
                        # copies only the dirty shards' slices (the
                        # packer lock is held, so no torn reads)
                        self._used_dev, nb = self._patch_shards(
                            self._used_dev, t.used, 0,
                            self._cache_npad,
                            self._shard_of(sync_rows, self._cache_npad))
                        h2d_bytes += nb
                        self.shard_h2d_bytes += nb
                        _registry().inc("nomad.engine.shard_h2d_bytes",
                                        nb)
                    self._used_version = ver
                    self._note_h2d(h2d_bytes,
                                   time.perf_counter() - t0h,
                                   "dirty-shard-patch")
                    return self._used_dev
            if deltas is not None:
                rows = np.concatenate([d[0] for d in deltas])
                vals = np.concatenate([d[1] for d in deltas])
                if len(rows):
                    _registry().inc("nomad.engine.used_replay_rows",
                                    len(rows), how="scatter")
                # aggregate per row first: a 100k-alloc plan touches far
                # fewer distinct rows; the upload shrinks with it
                if len(rows) > SCATTER_CHUNK:
                    uniq, inv = np.unique(rows, return_inverse=True)
                    agg = np.zeros((len(uniq), RES_DIMS), vals.dtype)
                    np.add.at(agg, inv, vals)
                    rows, vals = uniq, agg
                # fixed-size chunks -> one compiled scatter shape, ever
                # a small ladder of pad buckets: bounded compile count
                # (4 shapes ever) AND bounded upload waste (<= 4x).  The
                # ladder was sized for a ~3 MB/s link to the device and
                # has not been re-measured on a directly attached chip
                dev = self._used_dev
                for lo in range(0, len(rows), SCATTER_CHUNK):
                    r_c = rows[lo:lo + SCATTER_CHUNK]
                    v_c = vals[lo:lo + SCATTER_CHUNK]
                    n_c = len(r_c)
                    for pad in (512, 2048, 8192, SCATTER_CHUNK):
                        if n_c <= pad:
                            break
                    if pad != n_c:
                        r_c = np.concatenate(
                            [r_c, np.zeros(pad - n_c, r_c.dtype)])
                        v_c = np.concatenate(
                            [v_c, np.zeros((pad - n_c, RES_DIMS),
                                           v_c.dtype)])
                    dev = self._launch(
                        "scatter", (int(dev.shape[0]), pad),
                        self._scatter_fn,
                        dev, jnp.asarray(r_c), jnp.asarray(v_c))
                    h2d_bytes += r_c.nbytes + v_c.nbytes
                self._used_dev = dev
            else:
                # copy=True: t.used is mutated in place by the packer's
                # delta accounting; an aliased upload double-applies
                # future deltas
                used_h = t.used
                if self.mesh is None:
                    self._used_dev = jnp.array(used_h)
                else:
                    from jax.sharding import NamedSharding, PartitionSpec
                    self._used_dev = jax.device_put(
                        _pad_rows(np.array(used_h),
                                  self._padded_n(t.n)),
                        NamedSharding(self.mesh,
                                      PartitionSpec("nodes", None)))
                h2d_bytes += int(self._used_dev.nbytes)
            self._used_version = ver
            # the delta-log scatter replays stale usage after a chain
            # invalidation / plan commit; a full upload is the initial
            # (or post-rebuild) sync — two different costs the single
            # upload_bytes counter used to conflate
            self._note_h2d(h2d_bytes, time.perf_counter() - t0h,
                           "invalidation-replay" if deltas is not None
                           else "initial-upload")
            return self._used_dev

    def _used_to_fold(self, t: NodeTensors, cap: int):
        """What a launch needs to replay the usage deltas itself, in place
        of `_used_device`'s scatter: (the resident copy, its version, the
        version it reaches, the deltas between), read under the packer
        lock as `_used_device` reads them.  None where there is no
        resident copy, a rescan intervened, or the deltas pass `cap`
        rows: `_used_device` then does what it always did."""
        with self.packer.lock:
            base, base_ver = self._used_dev, self._used_version
            if base is None:
                return None
            ver = t.used_version
            deltas = ([] if base_ver == ver
                      else self.packer.used_deltas_since(base_ver))
            if deltas is None or sum(len(r) for r, _ in deltas) > cap:
                return None
            return base, base_ver, ver, deltas

    def _adopt_used(self, fold, used_next, seconds: float) -> None:
        """Take the launch's replayed `used` as the resident copy, at the
        version `_used_to_fold` read.  Not where another caller moved the
        copy meanwhile (a replay of its own, a full upload): the one it
        left is as new or newer.  The launch runs outside the packer
        lock, so the applier's commits never wait on it."""
        base, base_ver, ver, deltas = fold
        rows = sum(len(r) for r, _ in deltas)
        if rows:
            _registry().inc("nomad.engine.used_replay_rows", rows,
                            how="folded")
        with self.packer.lock:
            if ver != base_ver and self._used_dev is base:
                self._used_dev, self._used_version = used_next, ver
        self._note_h2d(rows * (1 + RES_DIMS) * 4, seconds,
                       "invalidation-replay")

    def _scan_ports(self, t: NodeTensors, npad: int, asks) -> dict:
        """The single-device scan's static-port fields as host arrays, to
        go up in its input buffer: `_lower_ports`' slots and holders for
        a launch with no chain behind it."""
        asked, ask = _port_slots(asks, len(asks))
        taken = np.zeros((ask.shape[1], npad), bool)
        for k, v in enumerate(asked):
            taken[k, self.packer.static_port_rows(t, v)[1]] = True
        return {"pt_taken0": taken, "pt_ask": ask}

    def _dev_const(self, key, builder):
        """Small per-eval tensors that repeat across evals (empty spread
        rows, zero job counts, dc/pool masks, the LUT matrix) — uploaded
        once and reused by cache key."""
        # LRU via dict insertion order: hits re-insert at the end so the
        # eviction prefix holds genuinely cold keys (stale version-embedded
        # masks), not the long-lived LUT matrix inserted at the first eval.
        # The packer lock guards against concurrent worker-thread eviction.
        with self.packer.lock:
            hit = self._const_cache.pop(key, None)
            if hit is not None:
                self._const_cache[key] = hit
                return hit
        val = jnp.asarray(builder())
        with self.packer.lock:
            if len(self._const_cache) > 256:
                for old in list(self._const_cache)[:64]:
                    self._const_cache.pop(old, None)
            self._const_cache[key] = val
        return val

    # -------------------------------------------------------------- solve

    def device_static_mask(self, t: NodeTensors, snapshot, req
                           ) -> np.ndarray:
        """The STATIC half of the DeviceChecker for one device request:
        a read-only [n] bool, True where the node carries a device group
        the request's name and constraints accept (scheduler/device.py
        group_feasible).  It moves with the node table alone, so it is
        built once per (request signature, node-table version), over the
        nodes that advertise a device at all, and reused after:
        `_candidate_frames`' pattern.  How many instances are FREE is
        the tensors' device dimension, the kernels' to account."""
        from nomad_tpu.scheduler.device import (group_accepts,
                                                request_signature)
        key = (t.version, request_signature(req))
        with self.packer.lock:
            hit = self._device_mask_cache.pop(key, None)
            if hit is not None:
                self._device_mask_cache[key] = hit
        if hit is not None:
            _registry().inc("nomad.engine.device_masks_reused")
            return hit
        mask = np.zeros(t.n, bool)
        memo: Dict[tuple, bool] = {}
        node_ids = t.node_ids
        for row in np.flatnonzero(t.dev_groups > 0).tolist():
            node = snapshot.node_by_id(node_ids[row])
            if node is not None and any(
                    group_accepts(dev, req, memo)
                    for dev in node.resources.devices):
                mask[row] = True
        mask.setflags(write=False)
        _registry().inc("nomad.engine.device_masks_built")
        with self.packer.lock:
            if len(self._device_mask_cache) >= 8:
                self._device_mask_cache.pop(
                    next(iter(self._device_mask_cache)))
            self._device_mask_cache[key] = mask
        return mask

    def _spread_landscapes(self, t: NodeTensors, sig: tuple,
                           spreads) -> tuple:
        """The STATIC half of a job's spread lowering
        (pack/spread.py spread_landscape): per stanza, each node's value
        index and the number of tracked values.  A function of the node
        table and the stanzas' (attribute, target values) alone, so it is
        walked once per (signature, node-table version) and shared by
        every eval that spreads alike: `device_static_mask`'s pattern.
        `sig` is `spread_signature(spreads)`."""
        key = (t.version, sig)
        with self.packer.lock:
            hit = self._spread_cache.pop(key, None)
            if hit is not None:
                self._spread_cache[key] = hit
        if hit is not None:
            _registry().inc("nomad.engine.spread_landscapes_reused")
            return hit
        out = tuple(spread_landscape(self.packer, t, sp) for sp in spreads)
        for nodeval, _ in out:
            nodeval.setflags(write=False)
        _registry().inc("nomad.engine.spread_landscapes_built")
        with self.packer.lock:
            if len(self._spread_cache) >= 8:
                self._spread_cache.pop(next(iter(self._spread_cache)))
            self._spread_cache[key] = out
        return out

    def _lower_wave_spreads(self, t: NodeTensors, npad: int, snapshot,
                            items: Sequence[BatchItem], g_pad: int):
        """The spread fields of MultiEvalInputs for a wave, or None where
        no item carries a stanza.  One [S, N] landscape a distinct
        signature goes up once a node-table version (`_dev_const`, as the
        base masks do); what is the job's own (weight, expected and
        existing counts) is a few floats an item.  S and K are padded to
        the wave's largest on the power-of-two ladder; row 0 of the
        landscapes is inert, for the items with no stanza."""
        by_item = [job_spreads(it.job) for it in items]
        if not any(by_item):
            return None
        if self.mesh is not None:
            raise ValueError("the sharded wave kernels carry no spread "
                             "state: a spread eval takes the solo path")
        with (self.timers.time("spread_lower") if self.timers is not None
              else contextlib.nullcontext()):
            sigs = [spread_signature(sp) for sp in by_item]
            lands = [self._spread_landscapes(t, sig, sp) if sp else ()
                     for sig, sp in zip(sigs, by_item)]
            s_pad = _pad_pow2(max(len(sp) for sp in by_item), lo=1)
            k_pad = _pad_pow2(max(k for ld in lands for _, k in ld), lo=1)
            g_spread = np.zeros(g_pad, np.int32)
            weight = np.zeros((g_pad, s_pad), np.float32)
            expected = np.zeros((g_pad, s_pad, k_pad), np.float32)
            counts0 = np.zeros((g_pad, s_pad, k_pad), np.float32)
            sig_rows: Dict[tuple, int] = {}
            rows = [self._dev_const(
                ("spreadland0", npad, s_pad),
                lambda: np.full((s_pad, npad), -1, np.int32))]
            for gi, (it, sp, sig, ld) in enumerate(
                    zip(items, by_item, sigs, lands)):
                if not sp:
                    continue
                ui = sig_rows.get(sig)
                if ui is None:
                    ui = sig_rows[sig] = len(rows)

                    def stacked(ld=ld):
                        out = np.full((s_pad, npad), -1, np.int32)
                        for i, (nodeval, _) in enumerate(ld):
                            out[i, :t.n] = nodeval
                        return out
                    rows.append(self._dev_const(
                        ("spreadland", t.version, npad, s_pad) + sig,
                        stacked))
                g_spread[gi] = ui
                w, exp, cnt = spread_job_rows(it.job, sp, ld, t, snapshot)
                weight[gi, :len(sp)] = w
                expected[gi, :len(sp), :exp.shape[1]] = exp
                counts0[gi, :len(sp), :cnt.shape[1]] = cnt
            rows.extend([rows[0]] * (_pad_pow2(len(rows), lo=1)
                                     - len(rows)))
            _registry().inc("nomad.spread.evals_batched",
                            sum(1 for sp in by_item if sp))
            return {"sp_nodeval": jnp.stack(rows),
                    "g_spread": jnp.asarray(g_spread),
                    "sp_weight": jnp.asarray(weight),
                    "sp_expected": jnp.asarray(expected),
                    "sp_counts0": jnp.asarray(counts0),
                    "by_item": by_item}

    def static_port_mask(self, t: NodeTensors, npad: int, value: int):
        """The nodes that HOLD static port `value` in the state, as a
        device [npad] bool: a live allocation that asked it, or the
        node's own reservation (pack/packer.py static_port_holders, kept
        by the alloc and block events that keep `used`).  Built once per
        (value, its holders' version, node-table version) and reused
        after: `device_static_mask`'s pattern."""
        version, rows = self.packer.static_port_rows(t, value)
        key = (t.version, npad, version)
        with self.packer.lock:
            hit = self._port_mask_cache.pop(value, None)
            if hit is not None and hit[0] == key:
                self._port_mask_cache[value] = hit
        if hit is not None and hit[0] == key:
            _registry().inc("nomad.engine.port_masks_reused")
            return hit[1]
        mask = np.zeros(npad, bool)
        mask[rows] = True
        dev = jnp.asarray(mask)
        _registry().inc("nomad.engine.port_masks_built")
        with self.packer.lock:
            if len(self._port_mask_cache) >= 64:
                self._port_mask_cache.pop(next(iter(self._port_mask_cache)))
            self._port_mask_cache[value] = (key, dev)
        return dev

    def _lower_ports(self, t: NodeTensors, npad: int, asks, g_pad: int,
                     carried=None):
        """The static-port fields of a launch (select.port_state), or
        None where no group asks a static port.  `asks[g]` are the
        values group g asks; the launch's slots are the values asked in
        it, in order, padded to the ladder, so a round pays for what its
        own launch asks and for nothing a chain has seen before.
        `carried` is the port state of the wave this launch is chained
        on, (values, their [Kp, npad] holders, {value: [npad] holders}
        of the values earlier waves of the chain asked and that one did
        not): a carried row stands for the state AND the chain's
        placements since, so it is taken as it is; any other value's
        holders are read from the state.  Returns (values, pt_taken0,
        pt_ask, rest): `rest` the carried rows this launch does not
        take, to hand on."""
        asked, pt_ask = _port_slots(asks, g_pad)
        if not asked:
            return None
        c_values, c_taken, rest = carried or ((), None, {})
        k_pad = pt_ask.shape[1]
        if asked == tuple(c_values) and c_taken.shape == (k_pad, npad):
            return asked, c_taken, jnp.asarray(pt_ask), rest
        # another set of values than the wave before: its rows join the
        # ones handed on, and this launch's are taken from among them
        rest = dict(rest)
        rest.update((v, c_taken[k]) for k, v in enumerate(c_values))
        rows = [rest.pop(v) if v in rest
                else self.static_port_mask(t, npad, v) for v in asked]
        while len(rest) > PORT_ROWS_CARRIED:
            rest.pop(next(iter(rest)))
        zrow = self._dev_const(("zrow", npad), lambda: np.zeros(npad, bool))
        rows.extend([zrow] * (k_pad - len(asked)))
        return asked, jnp.stack(rows), jnp.asarray(pt_ask), rest

    def single_group_fleet(self, t: NodeTensors) -> bool:
        """No node advertises more than one device group: then "instances
        in use on the node" is one number whatever a request's name, and
        the tensors' device dimension says exactly what a single request
        can take (the batched device path's admission rule)."""
        hit = self._single_group
        if hit is None or hit[0] != t.version:
            hit = self._single_group = (
                t.version, not bool((t.dev_groups > 1).any()))
        return hit[1]

    def _device_mask(self, tgs: Sequence[TaskGroup], t: NodeTensors,
                     snapshot, stopped_ids, device_in_use=None):
        """Host-side DeviceChecker analog (scheduler/device.py): a
        [G, N] bool mask of "node can satisfy this task group's device
        requests", ANDed into the kernel's static feasibility.  None when
        no group asks for devices (the common case — zero cost).

        A group's row is the AND of its requests' static masks
        (`device_static_mask`).  For ONE request on a fleet of
        single-group nodes that is all: the free count is the kernel's
        device dimension.  A group with several requests, or a fleet
        with a multi-group node, cannot be said in one number a node, so
        there the nodes the static masks admit are checked exactly on
        the host, against the instances their live allocations hold.

        `device_in_use` overlays in-plan assignments the snapshot can't
        see yet (the scheduler's retry loop threads it through so a node
        whose instances were consumed earlier in the same plan stops
        looking feasible): its nodes are checked exactly too."""
        from nomad_tpu.scheduler.device import (
            InUseIndex, node_feasible, tg_device_requests)
        reqs_by_g = [tg_device_requests(tg) for tg in tgs]
        if not any(reqs_by_g):
            return None
        single = self.single_group_fleet(t)
        overlay_rows = None
        if device_in_use is not None:
            overlay_rows = {t.id_to_row[nid]
                            for nid, _, _ in device_in_use.items()
                            if nid in t.id_to_row}
        in_use = InUseIndex()
        seeded: set = set()
        mask = np.ones((len(tgs), t.n), bool)
        for g, tg in enumerate(tgs):
            if not reqs_by_g[g]:
                continue
            row_mask = self.device_static_mask(t, snapshot,
                                               reqs_by_g[g][0][1])
            for _task, req in reqs_by_g[g][1:]:
                row_mask = row_mask & self.device_static_mask(
                    t, snapshot, req)
            mask[g] = row_mask
            if single and len(reqs_by_g[g]) == 1:
                exact = sorted(r for r in overlay_rows or ()
                               if row_mask[r])
            else:
                exact = np.flatnonzero(row_mask).tolist()
            for row in exact:
                node = snapshot.node_by_id(t.node_ids[row])
                if node is None:
                    mask[g, row] = False
                    continue
                if row not in seeded:
                    seeded.add(row)
                    for a in snapshot.allocs_by_node(node.id):
                        if not (a.terminal_status()
                                or a.id in stopped_ids):
                            in_use.add_alloc(node.id, a)
                    if device_in_use is not None:
                        for gid, ids in device_in_use.groups(node.id):
                            in_use.add(node.id, gid, ids)
                mask[g, row] = node_feasible(node, tg, in_use)
        return mask

    def place(self, snapshot, job: Job, tgs: Sequence[TaskGroup],
              requests: Sequence[PlacementRequest],
              tensors: Optional[NodeTensors] = None,
              stopped_allocs: Sequence = (),
              seed: int = 0,
              device_in_use=None,
              block=None,
              ):
        """Score + select nodes for `requests` (placements of `tgs`).

        `block`: compact alternative to `requests` — a (tg_name, count)
        pair describing `count` fresh placements of one task group with
        no per-placement state (reconcile.PlaceBlock).  The water-fill
        needs nothing more; if the job shape forces the exact scan
        (spread/distinct/devices) its rows are filled in here.

        Returns, for a water-fill (BULK_THRESHOLD or more placements of
        one group, nothing that asks the exact scan), one BulkDecisions;
        for a block off the exact scan, one BulkDecisions with a metric a
        placement as columns (`rows`), but where the group asks for
        devices; otherwise one PlacementDecision a request, in order.

        `stopped_allocs`: allocs the in-flight plan is stopping/evicting —
        their usage (and job-count, for this job) is subtracted before
        scoring, mirroring the reference's proposed-allocation view that
        folds plan.NodeUpdate into capacity (plan_apply.go evaluateNodePlan).

        `seed`: per-eval tie-break for equal-score nodes (the TPU-native
        analog of the reference's per-eval shuffled node order); without
        it concurrent workers pick identical nodes and the plan applier
        refutes all but the first (see select._tiebreak_noise).
        """
        # one "solo_place" stage interval per call (core/wavepipe.py)
        with (self.timers.time("solo_place") if self.timers is not None
              else contextlib.nullcontext()):
            return self._place(snapshot, job, tgs, requests, tensors,
                               stopped_allocs, seed, device_in_use, block)

    def _feasibility_fields(self, t: NodeTensors, npad: int, job: Job,
                            ctx: JobContext, tg_tensors: TGTensors):
        """What a job's static feasibility mask is computed from
        (feasibility.feasible_mask): (attrs, elig, dc_mask, pool_mask,
        con, luts), every one that repeats across evals cached on the
        device."""
        dev = self._node_arrays(t)
        return (
            dev["attrs"], dev["elig"],
            self._dev_const(("dc", t.version, npad, tuple(job.datacenters)),
                            lambda: _pad_rows(ctx.dc_mask, npad, False)),
            self._dev_const(("pool", t.version, npad, job.node_pool),
                            lambda: _pad_rows(ctx.pool_mask, npad, False)),
            tg_tensors.con,
            self._dev_const(
                ("luts", self.packer.lut_epoch, tg_tensors.luts.shape),
                lambda: tg_tensors.luts))

    def place_system(self, snapshot, job: Job, tgs: Sequence[TaskGroup],
                     node_ids: Sequence[str]):
        """A system eval's placement in one launch (ops/feasibility.py
        `place_system`): one allocation of every group of `tgs` on every
        node of `node_ids` that passes, over the resident node tensors
        and usage — no per-eval upload but the eval's own domain and
        constraint rows.  For groups that ask for no devices and no
        ports (the caller's to check: neither is a tensor here).

        Returns (tensors, rows, verdicts): `rows[i]` is `node_ids[i]`'s
        tensor row (-1: not in the tensors), `verdicts` the kernel's
        [G, n] int8 SYS_* table, fetched once.  One "system_place" stage
        interval per call (core/wavepipe.py)."""
        with (self.timers.time("system_place") if self.timers is not None
              else contextlib.nullcontext()):
            t = self.packer.update(snapshot)
            tg_tensors = self.packer.lower_task_groups(job, tgs,
                                                       snapshot=snapshot)
            ctx = self.packer.job_context(job, snapshot, t)
            npad = self._padded_n(t.n)
            row_of = t.id_to_row
            rows = np.fromiter((row_of.get(nid, -1) for nid in node_ids),
                               np.int64, len(node_ids))
            domain = np.zeros(npad, bool)
            domain[rows[rows >= 0]] = True
            dev = self._node_arrays(t)
            used = self._used_device(t)
            attrs, elig, dcm, pm, con, luts = self._feasibility_fields(
                t, npad, job, ctx, tg_tensors)
            verdicts = self._launch(
                "system", (len(tgs), con.shape[1], npad),
                place_system_jit, attrs, elig, dcm, pm, jnp.asarray(con),
                luts, dev["cap"], used, jnp.asarray(tg_tensors.req),
                jnp.asarray(domain))
            return t, rows, self._fetch(verdicts)[:, :t.n]

    def _place(self, snapshot, job, tgs, requests, tensors, stopped_allocs,
               seed, device_in_use, block):
        if block is not None:
            block_tg, block_count = block
            if block_count <= 0:
                return []
        elif not requests:
            return []
        t0 = time.perf_counter_ns()
        t = tensors if tensors is not None else self.packer.update(snapshot)
        n = t.n
        if n == 0:
            if block is not None:
                requests = [PlacementRequest(tg_name=block_tg)] * block_count
            return [self._no_nodes_decision(r, snapshot, job) for r in requests]

        tg_tensors: TGTensors = self.packer.lower_task_groups(
            job, tgs, snapshot=snapshot)
        name_to_g = {name: i for i, name in enumerate(tg_tensors.names)}
        p_real = block_count if block is not None else len(requests)
        # what the plan's stopped allocations give back, by node
        given_back: Dict[str, tuple] = {}
        for a in stopped_allocs:
            usage, own = given_back.get(a.node_id, (0, 0))
            given_back[a.node_id] = (np.add(usage, a.usage()),
                                     own + (a.job_id == job.id))

        # device (GPU/...) feasibility: host-computed per-TG node mask
        # (kernel capacity dims stay cpu/mem/disk; discrete instance
        # matching is host work — scheduler/device.py)
        dev_mask = self._device_mask(
            tgs, t, snapshot, {a.id for a in stopped_allocs}, device_in_use)
        has_dev_ask = dev_mask is not None
        port_asks = [tg_static_ports(tg) for tg in tgs]
        has_static = any(port_asks)
        has_spread = bool(job.spreads) or any(tg.spreads for tg in tgs)
        has_distinct = any(tg_tensors.distinct)
        if block is not None:
            bulk_ok = (p_real >= BULK_THRESHOLD
                       and not has_spread and not has_distinct
                       and not has_dev_ask and not has_static)
        else:
            bulk_ok = (
                p_real >= BULK_THRESHOLD
                and len({r.tg_name for r in requests}) == 1
                and not has_spread and not has_distinct
                and not has_static
                # device asks cap per-node intake by discrete instance
                # counts, which the water-fill rounds can't see — exact
                # scan only
                and not has_dev_ask
                and all(not r.prev_node_id for r in requests))
        if bulk_ok:
            return self._place_waterfill(
                snapshot, job, tgs, tg_tensors,
                name_to_g[block_tg if block is not None
                          else requests[0].tg_name],
                p_real, given_back, seed)

        ctx: JobContext = self.packer.job_context(job, snapshot, t)
        p_pad = _pad_pow2(p_real)
        npad = self._padded_n(n)
        desired = np.array([tg.count for tg in tgs], np.int32)
        algo = snapshot.scheduler_config().scheduler_algorithm
        dev = self._node_arrays(t)
        job_count = ctx.job_count
        stop_delta = None
        if given_back:
            stop_delta, jc_back = _stop_delta(t, npad, given_back)
            job_count = np.maximum(job_count - jc_back, 0)

        def used_on_device():
            used = self._used_device(t)
            if stop_delta is not None:
                used = used + jnp.asarray(stop_delta)
            return used

        # cached per-eval device constants: every [N]-sized upload that
        # repeats across evals is cached
        feas = self._feasibility_fields(t, npad, job, ctx, tg_tensors)
        if job_count.any():
            jc_dev = jnp.asarray(_pad_rows(job_count, npad))
        else:
            jc_dev = self._dev_const(("zjc", npad),
                                     lambda: np.zeros(npad, np.int32))

        # static port asks (ISSUE 38): the scan carries their holders
        # (select.port_state).  The sharded scan has none: there the
        # nodes that hold a value in the STATE leave through the mask,
        # and the eval's own placements keep to one a node by the
        # distinct_hosts limit (which counts the JOB's allocations on the
        # node: a second group of the job that asks no port is held off
        # it too)
        port_lost = None
        if has_static and self.mesh is not None:
            free = np.ones((len(tgs), n), bool)
            for g, values in enumerate(port_asks):
                for v in values:
                    free[g, self.packer.static_port_rows(t, v)[1]] = False
                if values and not tg_tensors.dh_limit[g]:
                    tg_tensors.dh_limit[g] = 1
            port_lost = (~free).sum(axis=1)
            dev_mask = free if dev_mask is None else dev_mask & free
        extra_mask = (None if dev_mask is None
                      else jnp.asarray(_pad_cols(dev_mask, npad, False)))

        # ONE packed device->host transfer: the chip sits behind a network
        # transport with a large fixed cost per array fetch, so the kernels
        # bitcast every output into a single int32 buffer.  used/job_count
        # stay on device, fetched only on the preemption fallback path.
        sp: SpreadTensors = lower_spreads(self.packer, job, t, snapshot)
        pd = self.packer.lower_distinct(job, tgs, tg_tensors, t, snapshot)
        tg_idx = np.zeros(p_pad, np.int32)
        prev_row = np.full(p_pad, -1, np.int32)
        active = np.zeros(p_pad, bool)
        if block is not None:
            # fresh placements of one group: no request rows exist
            tg_idx[:p_real] = name_to_g[block_tg]
            active[:p_real] = True
        else:
            for i, r in enumerate(requests):
                tg_idx[i] = name_to_g[r.tg_name]
                if r.prev_node_id:
                    prev_row[i] = t.id_to_row.get(r.prev_node_id, -1)
                active[i] = True
        # the per-eval fields as host arrays: on one device they go up
        # as ONE buffer (`select.pack_scan_inputs`), on a mesh each
        # as an array of its own
        inp = PlacementInputs(
            attrs=feas[0], cap=dev["cap"], used0=None, elig=feas[1],
            dc_mask=feas[2],
            pool_mask=feas[3],
            luts=feas[5],
            con=tg_tensors.con,
            aff=tg_tensors.aff,
            req=tg_tensors.req,
            desired=desired,
            dh_limit=tg_tensors.dh_limit,
            sp_nodeval=_pad_cols(sp.sp_nodeval, npad, -1),
            sp_weight=sp.sp_weight,
            sp_expected=sp.sp_expected,
            sp_counts0=sp.sp_counts0,
            pd_nodeval=_pad_cols(pd.pd_nodeval, npad, -1),
            pd_limit=pd.pd_limit,
            pd_apply=pd.pd_apply,
            pd_counts0=pd.pd_counts0,
            tg_idx=tg_idx,
            prev_row=prev_row,
            active=active,
            job_count0=jc_dev,
            spread_algo=np.bool_(algo == SCHED_ALGO_SPREAD),
            seed=np.uint32(seed & 0xFFFFFFFF),
            extra_mask=extra_mask,
        )
        if has_static and self.mesh is None:
            inp = inp._replace(**self._scan_ports(t, npad, port_asks))
        if self.mesh is not None:
            _registry().inc("nomad.engine.scan_inputs", 1, form="fields")
            inp = inp._replace(used0=used_on_device(), **{
                f: jnp.asarray(getattr(inp, f)) for f in
                SCAN_PACKED_FIELDS if getattr(inp, f) is not None})
            buf, used_dev, job_count_dev = self._launch(
                "scan", (npad, p_pad), self._sharded("scan"), inp)
            self._note_collective(
                p_pad, min(TOP_K, npad // self._ndev),
                width=2, extra=128)
        else:
            # what select.place_packed's trip count will meet: the
            # steps it runs, and the padding it passes by
            for kind, steps in (("run", p_real),
                                ("padded", p_pad - p_real)):
                _registry().inc("nomad.engine.scan_steps", steps,
                                kind=kind)
            # which scan `place_packed` takes at this shape, and why
            impl, why = scan_gate(inp)
            _registry().inc("nomad.engine.scan_launches", 1,
                            impl=impl, why=why)
            _registry().inc("nomad.engine.scan_inputs", 1, form="packed")
            # the usage replay rides the launch where the resident
            # copy has one to take and no stop is added to it
            fold = (None if stop_delta is not None
                    else self._used_to_fold(t, _fold_rows(npad)))
            if fold is None:
                inp = inp._replace(used0=used_on_device())
                deltas = ()
            else:
                inp = inp._replace(used0=fold[0])
                deltas = fold[3]
            t0h = time.perf_counter()
            layout, packed = pack_scan_inputs(inp, deltas,
                                              _fold_rows(npad))
            pack_s = time.perf_counter() - t0h
            buf, used_dev, job_count_dev, used_next = self._launch(
                "scan", (npad, p_pad), place_packed_jit,
                inp._replace(**dict.fromkeys(SCAN_PACKED_FIELDS)),
                packed, layout)
            if fold is not None:
                self._adopt_used(fold, used_next, pack_s)
        b = self._fetch(buf)[:p_real]
        picks = b[:, 0].copy()
        scores = b[:, 1].view(np.float32)
        topk_rows = b[:, 2:5]
        topk_scores = b[:, 5:8].view(np.float32)
        # nodes_filtered | nodes_exhausted | dimension_exhausted (and,
        # from a scan with static-port state, the nodes lost to it)
        counts = b[:, 9:].copy()
        counts[:, 0] -= npad - n
        if port_lost is not None:
            # the mask's nodes read as filtered: say what they are
            lost = port_lost[tg_idx[:p_real]].astype(counts.dtype)
            counts[:, 0] -= lost
            counts[:, 1] += lost
            counts = np.column_stack([counts, lost])
        elapsed = (time.perf_counter_ns() - t0) // max(p_real, 1)

        # ---- preemption fallback for failed placements ----
        evictions_by_req = self._preempt_fallback(
            picks, snapshot, job, feas, tg_tensors, tg_idx,
            t, used_dev, job_count_dev, p_real)

        # fresh placements of one group off the exact scan leave as
        # arrays, like the water-fill's (the scheduler commits them as
        # ONE AllocBlock).  A device ask keeps decisions: its instances
        # are assigned per placement (generic._assign_devices)
        as_block = block is not None and not has_dev_ask
        nodes = t.node_ids
        if as_block:
            # the block's metric columns name their candidates in a
            # table of their own: it outlives the node table, and rides
            # the wire
            uniq, inv = np.unique(topk_rows, return_inverse=True)
            nodes = [nodes[r] if r >= 0 else "" for r in uniq.tolist()]
            topk_rows = np.where(topk_rows >= 0, inv.reshape(
                topk_rows.shape), -1).astype(np.int32)
            topk_scores = topk_scores.copy()
        # the per-placement metrics stay the columns the kernel returned:
        # an AllocMetric is built where one is read
        rows = RowMetrics(
            nodes_evaluated=n, nodes_in_pool=int(ctx.pool_mask.sum()),
            nodes_available=self._dc_counts(t),
            allocation_time_ns=int(elapsed), counts=counts,
            topk=topk_rows, topk_scores=topk_scores, nodes=nodes,
            dim_names=RES_NAMES + ((port_collision_dimension(port_asks),)
                                   if counts.shape[1] > 2 + RES_DIMS
                                   else ()))
        if as_block:
            return BulkDecisions(
                tg_name=block_tg, picks=picks, node_ids=t.node_ids,
                round_size=1, metrics=[], evictions=evictions_by_req,
                nodes_evaluated=n, rows=rows, scores=scores.copy())
        if block is not None:
            # a decision a placement after all (a device ask): the
            # block's placements as request rows
            requests = [PlacementRequest(tg_name=block_tg)] * p_real
        node_ids = t.node_ids
        return [
            PlacementDecision(
                tg_name=r.tg_name,
                node_id=node_ids[pick] if pick >= 0 else None,
                score=score, metric=metric,
                evictions=evictions_by_req.get(i, []))
            for i, (r, pick, score, metric) in enumerate(zip(
                requests, picks.tolist(), scores.tolist(),
                rows.materialize()))]

    # device preemption: the victim tables are COMPACT (candidate nodes x
    # pow2 depth ladder), so the upload is bounded by live victims, not
    # cluster size — no node-count cap.  One launch per failing task
    # group; mixed-TG batches chain launches through the same usage
    # state.  The host Preemptor covers tiny batches,
    # >MAX_VICTIMS-deep nodes, oversized tables, and anything the
    # kernel left unplaced.
    PREEMPT_DEVICE_MIN_FAILED = 4
    # upload guard: candidates x depth x 16 B = ~4 MB.  Sized for a
    # ~3 MB/s link to the device; not re-measured on a directly
    # attached chip
    PREEMPT_DEVICE_MAX_TABLE = 256 * 1024

    def _preempt_fallback(self, picks, snapshot, job, feas, tg_tensors,
                          tg_idx, t, used_dev, job_count_dev, p_real
                          ) -> Dict[int, List]:
        """Preemption for placements the kernel could not fit (reference:
        BinPackIterator drives the Preemptor when Fit fails and preemption
        is enabled for the scheduler type).  `feas`: the static mask's
        fields (`_feasibility_fields`).  Mutates `picks`."""
        evictions_by_req: Dict[int, List] = {}
        if (not np.any(picks < 0)
                or not preemption_enabled(snapshot.scheduler_config(),
                                          job.type)):
            return evictions_by_req
        # slice off mesh padding rows: the preemptor works host-side over
        # the REAL node rows
        static = np.asarray(feasible_mask_jit(*feas))[:, :t.n]
        used = np.asarray(used_dev)[:t.n]
        job_count = np.asarray(job_count_dev)[:t.n]
        pre_evicted: set = set()

        failed = [i for i in range(p_real) if picks[i] < 0]
        by_g: Dict[int, list] = {}
        for i in failed:
            by_g.setdefault(int(tg_idx[i]), []).append(i)
        tables = None
        # victims consumed so far, per TENSOR row — shared across the
        # chained per-group launches: group k+1's tables must not offer
        # group k's victims again (each victim frees capacity ONCE;
        # reusing them overcommitted nodes — code-review r4 finding)
        taken: Dict[int, int] = {}
        for g, failed_g in sorted(by_g.items()):
            if len(failed_g) < self.PREEMPT_DEVICE_MIN_FAILED:
                continue
            if tables is None:
                from .preempt import build_victim_tables
                tables = build_victim_tables(job, snapshot, t)
            if (not tables[3]
                    or tables[1].size > self.PREEMPT_DEVICE_MAX_TABLE):
                break
            used, job_count = self._preempt_device(
                failed_g, g, tables, tg_tensors, t, static,
                used, job_count, picks, evictions_by_req, pre_evicted,
                taken)

        if not np.any(picks < 0):
            return evictions_by_req
        preemptor = Preemptor(job, snapshot, t, static, used,
                              job_count=job_count,
                              dh_limit=tg_tensors.dh_limit)
        preemptor.evicted_ids |= pre_evicted
        for i in range(p_real):
            if picks[i] >= 0:
                continue
            g = int(tg_idx[i])
            res = preemptor.preempt_for(g, tg_tensors.req[g].astype(np.int64))
            if res is not None:
                picks[i] = res.node_row
                evictions_by_req[i] = res.evictions
        return evictions_by_req

    def _preempt_device(self, failed, g, tables, tg_tensors, t,
                        static, used, job_count, picks, evictions_by_req,
                        pre_evicted, taken):
        """One preempt_bulk launch for ONE task group's failed batch over
        the compact candidate tables; maps (candidate, k) results back to
        concrete victim allocs.  `taken` (tensor row -> victims consumed)
        persists across the chained per-group launches: consumed victim
        prefixes are MASKED out of this launch's tables.  Returns the
        post-eviction (used, job_count) with the kernel's compact
        updates scattered back to cluster rows."""
        from .preempt import preempt_bulk_jit
        cand_rows, prio, res, by_row = tables
        # victims consumed by earlier groups start CONSUMED in the
        # kernel (prefix-ordered), so they neither free capacity twice
        # nor inflate the per-placement victim counts
        k0 = np.zeros(len(cand_rows), np.int32)
        if taken:
            for ci, row in enumerate(cand_rows):
                k0[ci] = taken.get(int(row), 0)
        # compact the cluster-shaped inputs to candidate rows (host-side
        # numpy gathers; the upload shrinks with them), padding the
        # candidate axis on the pow2 ladder so the kernel compiles per
        # SHAPE BUCKET, not per eval (raw m changes nearly every eval)
        m = len(cand_rows)
        m_pad = _pad_pow2(m)
        def padr(a, fill=0):
            out = np.full((m_pad,) + a.shape[1:], fill, a.dtype)
            out[:m] = a
            return out
        cap_c = padr(t.cap[cand_rows])
        used_c = padr(used[cand_rows])
        static_c = padr(static[g][cand_rows], False)
        jc_c = padr(job_count[cand_rows])
        prio_p = padr(prio, 1 << 30)
        res_p = padr(res)
        k0_p = padr(k0)
        req = tg_tensors.req[g].astype(np.int32)
        best_c, ks, used2_c, jc2_c = preempt_bulk_jit(
            jnp.asarray(cap_c), jnp.asarray(used_c),
            jnp.asarray(static_c),
            jnp.asarray(tg_tensors.dh_limit[g]),
            jnp.asarray(jc_c),
            jnp.asarray(prio_p), jnp.asarray(res_p), jnp.asarray(req),
            jnp.asarray(k0_p),
            _pad_pow2(len(failed)), jnp.asarray(len(failed), jnp.int32))
        best_c = np.asarray(best_c)
        ks = np.asarray(ks)
        # scatter the compact usage updates back to cluster rows
        used = used.copy()
        used[cand_rows] = np.asarray(used2_c)[:m]
        job_count = job_count.copy()
        job_count[cand_rows] = np.asarray(jc2_c)[:m]
        for j, i in enumerate(failed):
            ci = int(best_c[j])
            if ci < 0:
                continue
            row = int(cand_rows[ci])
            k = int(ks[j])
            start = taken.get(row, 0)
            victims = by_row[row][start:start + k]
            taken[row] = start + k
            picks[i] = row
            evictions_by_req[i] = victims
            pre_evicted.update(v.id for v in victims)
        return used, job_count

    def _dc_counts(self, t: NodeTensors) -> Dict[str, int]:
        """Ready-node count per datacenter (AllocMetric.nodes_available),
        computed vectorized from the packed tensors and cached per row
        version — the object-walk over 50k nodes cost more than the kernel."""
        if self._dc_cache is not None and self._dc_cache[0] == t.version:
            return self._dc_cache[1]
        counts: Dict[str, int] = {}
        if t.n:
            bc = np.bincount(t.dc[t.elig])
            for vid in np.nonzero(bc)[0]:
                counts[self.packer.interner.string(int(vid))] = int(bc[vid])
        self._dc_cache = (t.version, counts)
        return counts

    def _place_waterfill(self, snapshot, job: Job, tgs, tg_tensors, g: int,
                         p_real: int, given_back, seed: int) -> BulkDecisions:
        """A solo eval's water-fill: its `p_real` fresh placements of
        group `g` as a wave of ONE item on the flat multi-eval kernel (or
        its sharded twin on a mesh), the allocations its plan stops gone
        from the state the launch sees, then the preemption fallback for
        the placements the kernel could not fit."""
        tg = next(x for x in tgs if x.name == tg_tensors.names[g])
        pending = self.dispatch_batch(
            snapshot, [BatchItem(job=job, tg=tg, count=p_real)], seed=seed,
            stopped=given_back)
        (bd,) = self.collect_batch(pending)
        t = pending["t"]
        bd.evictions = self._preempt_fallback(
            bd.picks, snapshot, job,
            self._feasibility_fields(t, pending["npad"], job,
                                     pending["ctxs"][0], tg_tensors),
            tg_tensors, np.full(p_real, g, np.int32), t, pending["used"],
            pending["job_count"], p_real)
        return bd

    @staticmethod
    def _metrics_from_meta(meta, n, n_in_pool, dc_counts, node_ids,
                           elapsed, port_dim: str = "") -> List[AllocMetric]:
        """Per-round AllocMetric objects from the water-fill kernels'
        compact meta block (collect_batch).
        `port_dim`: for an item that asks a static port off a wave with
        port state, the dimension that names the nodes its rounds lost
        to it (the meta block's column 14)."""
        tsc = meta[:, 3:6].view(np.float32).tolist()
        metrics: List[AllocMetric] = []
        for r, row in enumerate(meta.tolist()):
            metric = AllocMetric(
                nodes_evaluated=n,
                nodes_filtered=row[7],
                nodes_in_pool=n_in_pool,
                nodes_available=dc_counts,
                nodes_exhausted=row[8],
                allocation_time_ns=elapsed,
            )
            for d, col in enumerate(_META_DIM_EX):
                if row[col]:
                    metric.dimension_exhausted[RES_NAMES[d]] = row[col]
            if port_dim and row[_META_PORT_EX]:
                metric.dimension_exhausted[port_dim] = row[_META_PORT_EX]
            metric.score_meta_data = [
                NodeScoreMeta(node_id=node_ids[kr],
                              scores={"final": ks}, norm_score=ks)
                for kr, ks in zip(row[0:3], tsc[r]) if kr >= 0]
            metrics.append(metric)
        return metrics

    # -------------------------------------------------------- multi-eval

    def place_batch(self, snapshot, items: Sequence[BatchItem],
                    seed: int = 0) -> List[Optional[BulkDecisions]]:
        """Score + select nodes for MANY evals' placement blocks in ONE
        device launch (DP over evals — SURVEY §3.6 row 1; the reference
        runs one eval per worker goroutine instead, nomad/worker.go).

        Each item is one eval's (job, task group, count) block; rounds
        run sequentially on device so the items' plans see each other's
        proposed usage and cannot refute each other at the applier.
        `seed` may be a single int (broadcast) or one per item — the
        worker passes each eval's solo-path seed so batched picks match
        the serial path tie-for-tie.
        Returns one BulkDecisions per item (None when the cluster is
        empty).  Preemption is NOT attempted here — a caller seeing
        failed picks with preemption enabled should fall back to the
        single-eval path, which carries the preemptor."""
        pending = self.dispatch_batch(snapshot, items, seed=seed)
        return self.collect_batch(pending)

    def dispatch_batch(self, snapshot, items: Sequence[BatchItem],
                       seed: int = 0, used0_dev=None,
                       masked_node_ids=None, stopped=None):
        """Asynchronous half of place_batch: pack + LAUNCH the kernel and
        return a pending handle (kernel dispatch does not block; the
        device computes while the host does other work — collect_batch
        blocks on the result).

        `used0_dev`: a (usage array, node-table version, padded-n) triple
        to start from INSTEAD of the packer-synced state — the
        cross-batch chaining hook: a worker may hand batch k's
        proposed-usage output in so batch k+1 computes against it before
        batch k's plans commit.  Proposed usage is a SUPERSET of
        committed usage (refuted/no-op plans only release capacity), so
        chained decisions can under-pack but never oversubscribe.  The
        version/padding guard matters: a node-table rebuild (membership
        or attribute change) remaps rows, and per-node usage applied to
        remapped rows would credit load to the wrong nodes — on any
        mismatch the chain falls back to the packer-synced tensor.
        Accepted chains launch through the DONATED-usage jit variants
        (select.place_multi_chained): the previous wave's buffer is dead
        once consumed, so XLA reuses its allocation in place.

        `masked_node_ids`: node ids excluded from this launch's
        eligibility — the wave pipeline's refute-repair input
        (core/wavepipe.py): a chained launch's usage buffer predates the
        foreign write that refuted these nodes, so masking is the only
        way the kernel can avoid re-picking them.

        `stopped`: see build_multi_inputs (the solo path's one-item
        launch)."""
        if not items:
            return None
        # per-dispatch dirty-shard upload meter: build_multi_inputs pays
        # any shard patches this launch needs; the delta rides the
        # pending dict so the wave pipeline's flight record carries the
        # per-wave figure without a second engine read
        shard_b0 = self.shard_h2d_bytes
        built = self.build_multi_inputs(snapshot, items, seed=seed,
                                        used0_dev=used0_dev,
                                        masked_node_ids=masked_node_ids,
                                        stopped=stopped)
        if isinstance(built, tuple):
            return built                 # empty-cluster sentinel
        inp, rs, aux = built["inp"], built["rs"], built
        chained = aux.get("chained", False)
        compact = aux["cand_rows"] is not None
        skey = (rs, aux["npad"], aux["n_lanes"])
        # one of four programs, on either deployment: the compact laned
        # kernel or the flat one, fresh or chained.  A chained launch goes
        # through the DONATED-usage variant: the previous wave's usage
        # buffer is dead once consumed, so XLA reuses it in place
        kind = (("multi_compact" if compact else "multi")
                + ("_chained" if chained else ""))
        statics = (rs, aux["n_lanes"]) if compact else (rs,)
        args = (inp.used0, inp._replace(used0=None)) if chained else (inp,)
        if compact:
            args += tuple(aux["cand_dev"])
        coll_bytes = 0
        if self.mesh is not None:
            fn = self._sharded(kind, *statics)
            # the host's side of a sharded launch: the wave's replicated
            # inputs go to every device and one execution a device is
            # enqueued (inside the wave's `dispatch`, as `spread_lower`)
            with (self.timers.time("mesh_launch") if self.timers is not None
                  else contextlib.nullcontext()):
                out = self._launch(kind, skey, fn, *args)
            coll_bytes = self._note_collective(
                int(inp.round_g.shape[0]),
                min(rs, int(aux["cand_rows"].shape[-1]) if compact
                    else aux["npad"] // self._ndev))
        else:
            out = self._launch(kind, skey, _WAVE_JITS[kind], *args, *statics)
        fills_full = fill_k = jc_out = None
        if compact:
            buf, fills_full, used_out = out
            fill_k = min(FILL_K, rs)
        else:
            buf, used_out, jc_out, *taken_out = out
        # the static-port state a wave chained on this one starts from:
        # what this launch left, or what it was handed and did not touch
        port_state = ((aux["ports"][0], taken_out[0], aux["ports"][1])
                      if aux["ports"] is not None
                      else aux["carried_ports"])
        # start the device->host copy of the result buffer NOW: queued
        # behind the compute, a prefetched batch's transfer rides out
        # the PREVIOUS batch's host phase instead of blocking collect
        buf.copy_to_host_async()
        # prep_ns, not a wall t0: a prefetched batch may sit dispatched
        # while the PREVIOUS batch's host phase runs — that gap is not
        # scheduling time and must not inflate AllocMetric latency
        # `job_count`: the flat kernel's last real round's count row (the
        # solo path's preemption fallback reads it)
        return {"buf": buf, "used": used_out, "job_count": jc_out,
                "items": list(items),
                "ports": port_state, "port_asks": aux["port_asks"],
                "spans": aux["spans"], "counts": aux["counts"], "rs": rs,
                "item_rs": aux["item_rs"], "rounds": aux["rounds"],
                "rounds_padded": aux["rounds_padded"],
                "t": aux["t"], "ctxs": aux["ctxs"], "n": aux["n"],
                "npad": aux["npad"], "node_version": aux["t"].version,
                "perm": aux["perm"], "fills_full": fills_full,
                "fill_k": fill_k, "chained": chained,
                "collective_bytes": coll_bytes,
                "mesh_devices": self._ndev,
                "shard_h2d_bytes": self.shard_h2d_bytes - shard_b0,
                "padded_fraction":
                    (aux["npad"] - aux["n"]) / aux["npad"],
                "prep_ns": time.perf_counter_ns() - aux["t0"]}

    def build_multi_inputs(self, snapshot, items: Sequence[BatchItem],
                           seed: int = 0, used0_dev=None,
                           masked_node_ids=None, stopped=None):
        """Host half of dispatch_batch: pack + lower a multi-eval batch
        into MultiEvalInputs WITHOUT launching (bench.py --kernel times
        the production kernel on exactly these inputs).  Returns a dict
        {inp, rs, spans, counts, t, ctxs, n, npad, t0, chained} or the
        empty-cluster sentinel tuple.

        `masked_node_ids` (wavepipe refute-repair): these nodes are
        dropped from the launch's eligibility — ANDed into the device
        elig tensor for the flat/sharded kernels and into the host-side
        signature masks the compact candidate frames are built from, so
        both kernel layouts honor the mask identically.

        `stopped` (the solo path's one-item launch, never chained): the
        allocations its plan stops, as `_stop_delta` takes them — their
        usage leaves the launch's `used` and the job's own leave its
        count row, as the exact scan sees them.  None adds nothing."""
        from nomad_tpu.scheduler.device import request_signature
        t = self.packer.update(snapshot)
        n = t.n
        if n == 0:
            return (None, items)
        t0 = time.perf_counter_ns()
        npad = self._padded_n(n)
        dev = self._node_arrays(t)
        used0 = None
        carried_ports = None
        if used0_dev is not None:
            # (usage, node version, padded n) and, from a wave that had
            # or carried static-port state, that state
            arr, chain_ver, chain_npad, *chain_ports = used0_dev
            if chain_ver == t.version and chain_npad == npad:
                used0 = arr
                carried_ports = chain_ports[0] if chain_ports else None
        chained = used0 is not None
        if used0 is None:
            used0 = self._used_device(t)
        jc_back = None
        if stopped:
            stop_delta, jc_back = _stop_delta(t, npad, stopped)
            used0 = used0 + jnp.asarray(stop_delta)
        # refuted-node mask: host bool overlay ANDed into eligibility
        # (one tiny upload; the node tensor caches stay untouched)
        elig_dev = dev["elig"]
        node_ok = None
        if masked_node_ids:
            rows = np.array([t.id_to_row[nid] for nid in masked_node_ids
                             if nid in t.id_to_row], np.int64)
            if rows.size:
                node_ok = np.ones(npad, bool)
                node_ok[rows] = False
                elig_dev = elig_dev & jnp.asarray(node_ok)
        algo = snapshot.scheduler_config().scheduler_algorithm

        G = len(items)
        g_pad = _pad_pow2(G, lo=1)
        # per-item tie-break seeds (select.MultiEvalInputs.seed): a
        # scalar broadcasts (legacy callers / bench); the worker passes
        # one seed per eval — the SAME value the eval's solo launch
        # would use — so batched and solo paths draw identical noise
        # and the wave pipeline's serial/pipelined parity is exact
        if np.ndim(seed) == 0:
            seed_g = np.full(g_pad, int(seed) & 0xFFFFFFFF, np.uint32)
        else:
            seeds = [int(s) & 0xFFFFFFFF for s in seed]
            if len(seeds) != G:
                raise ValueError(
                    f"per-item seeds: got {len(seeds)} for {G} items")
            seed_g = np.zeros(g_pad, np.uint32)
            seed_g[:G] = seeds
        tgts = []
        ctxs = []
        for it in items:
            tgts.append(self.packer.lower_task_groups(
                it.job, [it.tg], snapshot=snapshot))
            ctxs.append(self.packer.job_context(it.job, snapshot, t))
        # pad the constraint/affinity row axes to a pow2 ladder so mixed
        # batches land on a handful of compiled shapes
        c_max = _pad_pow2(max(tt.con.shape[1] for tt in tgts), lo=1)
        a_max = _pad_pow2(max(tt.aff.shape[1] for tt in tgts), lo=1)
        req = np.zeros((g_pad, RES_DIMS), np.int32)
        desired = np.ones(g_pad, np.int32)
        dh_limit = np.zeros(g_pad, np.int32)
        # Constraint/affinity signatures dedupe across the batch: the
        # kernel evaluates ONE [N] landscape per distinct signature and
        # rounds index into them (a uniform 384-eval batch carries ~5).
        g_static = np.zeros(g_pad, np.int32)
        g_aff = np.zeros(g_pad, np.int32)
        static_keys: Dict[bytes, int] = {}
        static_con: List[np.ndarray] = []
        static_mi: List[int] = []
        aff_keys: Dict[bytes, int] = {}
        aff_rows: List[np.ndarray] = []
        mask_keys: Dict[tuple, int] = {}
        mask_rows: List[object] = []
        mask_np: List[np.ndarray] = []   # host copies for lane scheduling
        jc_nz_idx: List[int] = []
        jc_nz_rows: List[np.ndarray] = []
        for gi, it in enumerate(items):
            tt, ctx = tgts[gi], ctxs[gi]
            req[gi] = tt.req[0]
            desired[gi] = max(it.tg.count, 1)
            dh_limit[gi] = tt.dh_limit[0]
            # a device request's static mask (which nodes carry a group
            # it accepts) is one more [N] landscape of the node table:
            # it joins the datacenter and pool masks, so a signature
            # still names everything static about the item's nodes
            dev_reqs = [d for task in it.tg.tasks
                        for d in task.resources.devices]
            key = (tuple(it.job.datacenters), it.job.node_pool) + tuple(
                request_signature(d) for d in dev_reqs)
            mi = mask_keys.get(key)
            if mi is None:
                mi = len(mask_rows)
                mask_keys[key] = mi
                host_mask = ctx.dc_mask & ctx.pool_mask
                for d in dev_reqs:
                    host_mask = host_mask & self.device_static_mask(
                        t, snapshot, d)
                mask_rows.append(self._dev_const(
                    ("basemask", t.version, npad) + key,
                    lambda m=host_mask: _pad_rows(m, npad, False)))
                mask_np.append(host_mask)
            con_row = np.zeros((c_max, 3), np.int32)
            con_row[:tt.con.shape[1]] = tt.con[0]
            skey = con_row.tobytes() + mi.to_bytes(4, "little")
            si = static_keys.get(skey)
            if si is None:
                si = len(static_con)
                static_keys[skey] = si
                static_con.append(con_row)
                static_mi.append(mi)
            g_static[gi] = si
            aff_row = np.zeros((a_max, 4), np.int32)
            aff_row[:tt.aff.shape[1]] = tt.aff[0]
            akey = aff_row.tobytes()
            ai = aff_keys.get(akey)
            if ai is None:
                ai = len(aff_rows)
                aff_keys[akey] = ai
                aff_rows.append(aff_row)
            g_aff[gi] = ai
            jc = (ctx.job_count if jc_back is None
                  else np.maximum(ctx.job_count - jc_back, 0))
            if jc.any():
                jc_nz_idx.append(gi)
                jc_nz_rows.append(jc)
        m_pad = _pad_pow2(len(mask_rows), lo=1)
        zrow = self._dev_const(("zrow", npad),
                               lambda: np.zeros(npad, bool))
        mask_rows.extend([zrow] * (m_pad - len(mask_rows)))
        base_mask = jnp.stack(mask_rows)
        u_pad = _pad_pow2(len(static_con), lo=1)
        con = np.zeros((u_pad, c_max, 3), np.int32)
        u_mask = np.zeros(u_pad, np.int32)
        for si, row in enumerate(static_con):
            con[si] = row
            u_mask[si] = static_mi[si]
        ua_pad = _pad_pow2(len(aff_rows), lo=1)
        aff = np.zeros((ua_pad, a_max, 4), np.int32)
        for ai, row in enumerate(aff_rows):
            aff[ai] = row

        # round schedule: item gi -> ceil(count / rs) consecutive rounds.
        # The ladder matters: a water-fill round's cost is dominated by
        # top_k(N, rs), which the TPU lowers to a full sort of the nodes,
        # and the [R, rs+16] buffer transfer, so the smallest bucket
        # covering the biggest item wins (finer buckets would multiply
        # compiles).
        # An item with a spread stanza takes ONE ROUND A PLACEMENT: the
        # boost moves with every commit, so a `want`-1 round is the exact
        # scan's step (same mask, same mean of the same components, same
        # noise, arg-max) and `item_rs` says how its rows expand.  The
        # flat kernel takes such a round by arg-max, with no sort
        # (select.pick_one_round).
        counts = [max(it.count, 0) for it in items]
        spread = self._lower_wave_spreads(t, npad, snapshot, items, g_pad)
        port_asks = [tg_static_ports(it.tg) for it in items]
        if any(port_asks) and self.mesh is not None:
            raise ValueError("the sharded wave kernels carry no static-port "
                             "state: such an eval takes the solo path")
        ports = self._lower_ports(t, npad, port_asks, g_pad, carried_ports)
        biggest = max(counts) if counts else 0
        for rs in (64, 256, 512, 1024):
            if biggest <= rs:
                break
        item_rs = [1 if spread is not None and spread["by_item"][gi]
                   else rs for gi in range(G)]
        round_g: List[int] = []
        round_want: List[int] = []
        spans: List[Tuple[int, int]] = []
        for gi, c in enumerate(counts):
            start = len(round_g)
            left = c
            while left > 0:
                round_g.append(gi)
                round_want.append(min(left, item_rs[gi]))
                left -= item_rs[gi]
            spans.append((start, len(round_g)))
        if spread is not None:
            _registry().inc("nomad.spread.rounds", sum(
                c for c, irs in zip(counts, item_rs) if irs == 1))

        # ---- compact lane-parallel schedule (round-5 verdict #2/#3) ----
        # When the batch's signatures form ONE clique of pairwise
        # PROVABLY-DISJOINT static landscapes (the bench's per-zone CSI
        # topology LUTs; any constraints pinning one attribute to
        # different values), each signature gets a lane + a compact
        # candidate frame and the rounds run one-per-lane concurrently:
        # sequential depth drops R → R/L and per-round work drops N → Nc.
        # On a mesh the frames additionally split by OWNER SHARD
        # ([S, L, Nc_loc]; parallel/mesh._multi_compact_local) so the
        # laned fast path composes with node-axis sharding.  Any batch
        # whose disjointness the structural prover cannot establish keeps
        # the flat sequential schedule.
        n_real = len(round_g)
        n_lanes = 1
        perm = None
        cand_rows = cand_valid = cand_dev = None
        luts = tgts[-1].luts      # the most complete LUT matrix
        # (a wave that holds a spread item or a static port ask keeps the
        # flat schedule: the laned kernel carries neither state)
        if (n_real > 1 and len(static_con) > 1 and spread is None
                and ports is None):
            weights = [0] * len(static_con)
            for r_idx in range(n_real):
                weights[int(g_static[round_g[r_idx]])] += 1
            cliques = _disjoint_cliques(static_con, luts, weights)
            # one clique of WIDTH > 1: single-signature batches stay on
            # the flat kernel (no lane parallelism to win, and flat is
            # what the mesh/bridge parity suites pin)
            if len(cliques) == 1 and len(cliques[0]) > 1:
                # lanes in the order of their signatures' bytes, not of
                # the wave's weights: lanes are symmetric in the kernel
                # (disjoint frames, per-item seeds, rows mapped back by
                # `perm`), and a drain's waves weigh the same signatures
                # differently each time, so any other order would give
                # every wave frames of its own
                mask_key_of = list(mask_keys)
                clique = sorted(cliques[0], key=lambda s: (
                    static_con[s].tobytes(), mask_key_of[static_mi[s]]))
                width = len(clique)
                cand_rows, cand_valid, cand_dev = self._candidate_frames(
                    t, npad, luts, [static_con[s] for s in clique],
                    [mask_key_of[static_mi[s]] for s in clique],
                    [mask_np[static_mi[s]] for s in clique], node_ok)
                nc = cand_rows.shape[-1]
                lane_of = {s: li for li, s in enumerate(clique)}
                lanes: List[List[int]] = [[] for _ in range(width)]
                for r_idx in range(n_real):
                    si = int(g_static[round_g[r_idx]])
                    lanes[lane_of[si]].append(r_idx)
                t_c = max(len(ln) for ln in lanes)
                t_pad = _pad_pow2(t_c, lo=1)
                sched_g: List[int] = []
                sched_want: List[int] = []
                perm = np.zeros(n_real, np.int64)
                for t_i in range(t_pad):
                    for li in range(width):
                        pos = len(sched_g)
                        if t_i < len(lanes[li]):
                            r_idx = lanes[li][t_i]
                            sched_g.append(round_g[r_idx])
                            sched_want.append(round_want[r_idx])
                            perm[r_idx] = pos
                        else:
                            # inert: repeat the lane's previous g
                            # (want=0 commits nothing; keeping the same
                            # g preserves job-count chains)
                            prev = (sched_g[pos - width]
                                    if pos >= width else 0)
                            sched_g.append(prev)
                            sched_want.append(0)
                n_lanes = width
                round_g, round_want = sched_g, sched_want

        pad_r = 0
        if cand_rows is None:
            r_pad = _pad_pow2(max(len(round_g), 1), lo=1)
            pad_r = r_pad - len(round_g)
            if self.mesh is None:
                # what select.place_multi_packed's branch on `want` will
                # meet: an arg-max, a water-fill, a round that runs nothing
                pick_one = round_want.count(1)
                for kind, rounds in (("pick_one", pick_one),
                                     ("fill", n_real - pick_one),
                                     ("padded", pad_r)):
                    _registry().inc("nomad.engine.rounds", rounds, kind=kind)
            round_g.extend([0] * pad_r)
            round_want.extend([0] * pad_r)

        # per-job alloc-count seeds.  Compact path: a tiny [J', Nc] table
        # (row 0 = zeros shared by every fresh job; one gathered row per
        # job with live allocs) — the kernel gathers L rows per step.
        # Flat path: device zeros [G, N] + a scatter of only the nonzero
        # jobs (fresh jobs upload nothing).  The old [G, N] table cost a
        # 76ms gather of mostly zeros per launch at bench scale.
        if cand_rows is not None:
            g_job = np.zeros(g_pad, np.int32)
            jrows = [np.zeros(cand_rows.shape[:-2] + (nc,), np.int32)]
            if jc_nz_idx:
                for gi, jc_row in zip(jc_nz_idx, jc_nz_rows):
                    li = lane_of[int(g_static[gi])]
                    idx = cand_rows[..., li, :]    # [nc] or [S, nc]
                    row = np.where(idx < n,
                                   jc_row[np.minimum(idx, n - 1)], 0)
                    g_job[gi] = len(jrows)
                    jrows.append(row.astype(np.int32))
            jc0 = np.stack(jrows)
            if cand_rows.ndim == 3:
                # sharded seeds: [S, J', Nc_loc] (J' axis second)
                jc0 = np.moveaxis(jc0, 0, 1)
            jc0 = jnp.asarray(jc0)
            g_job_dev = jnp.asarray(g_job)
        else:
            jc0 = jnp.zeros((g_pad, npad), jnp.int32)
            if jc_nz_idx:
                jc0 = jc0.at[
                    jnp.asarray(np.array(jc_nz_idx, np.int32))].set(
                    jnp.asarray(_pad_cols(np.stack(jc_nz_rows), npad)))
            g_job_dev = jnp.arange(g_pad, dtype=jnp.int32)

        luts_dev = self._dev_const(
            ("luts", self.packer.lut_epoch, luts.shape), lambda: luts)

        inp = MultiEvalInputs(
            attrs=dev["attrs"], cap=dev["cap"], used0=used0,
            elig=elig_dev, luts=luts_dev, base_mask=base_mask,
            con=jnp.asarray(con), u_mask=jnp.asarray(u_mask),
            aff=jnp.asarray(aff),
            req=jnp.asarray(req), desired=jnp.asarray(desired),
            dh_limit=jnp.asarray(dh_limit),
            g_static=jnp.asarray(g_static), g_aff=jnp.asarray(g_aff),
            g_job=g_job_dev,
            job_count0=jc0,
            spread_algo=jnp.asarray(algo == SCHED_ALGO_SPREAD),
            round_g=jnp.asarray(np.array(round_g, np.int32)),
            round_want=jnp.asarray(np.array(round_want, np.int32)),
            seed=jnp.asarray(seed_g),
        )
        if spread is not None:
            inp = inp._replace(**{k: spread[k] for k in (
                "sp_nodeval", "g_spread", "sp_weight", "sp_expected",
                "sp_counts0")})
        if ports is not None:
            inp = inp._replace(pt_taken0=ports[1], pt_ask=ports[2])
        return {"inp": inp, "rs": rs, "spans": spans, "counts": counts,
                # the launch's static port values in slot order with the
                # rows it hands on untouched, or, on a wave that asks
                # none, the chain's state to hand on as is
                "ports": ports and (ports[0], ports[3]),
                "port_asks": port_asks, "carried_ports": carried_ports,
                "item_rs": item_rs, "rounds": n_real, "rounds_padded": pad_r,
                "t": t, "ctxs": ctxs, "n": n, "npad": npad, "t0": t0,
                "n_lanes": n_lanes, "perm": perm, "chained": chained,
                "cand_rows": cand_rows, "cand_valid": cand_valid,
                "cand_dev": cand_dev}

    def _candidate_frames(self, t: NodeTensors, npad: int, luts,
                          con_by_lane, mask_key_by_lane, base_by_lane,
                          node_ok):
        """The compact path's candidate frames: per lane, the rows of
        the nodes its signature's static mask admits, padded ([L, Nc];
        on a mesh split by owner shard, [S, L, Nc_loc]).  Returns
        (cand_rows, cand_valid, their device copies).

        Everything here is derived from the node table, the LUT matrix
        and the signatures alone, so it is derived when one of those
        changes and not when a wave launches: `t.version` moves with
        every row write and rebuild, `lut_epoch` and the matrix's shape
        with every LUT row and vocabulary growth, and `base_by_lane` is
        `job_context`'s per-version mask of `mask_key_by_lane`.
        `node_ok` (refute repair) is one launch's overlay: such a launch
        builds its own frames and leaves none behind."""
        key = (t.version, npad, self._ndev, self.packer.lut_epoch,
               luts.shape, tuple(mask_key_by_lane),
               tuple(c.tobytes() for c in con_by_lane))
        if node_ok is None:
            with self.packer.lock:
                hit = self._frame_cache.pop(key, None)
                if hit is not None:
                    self._frame_cache[key] = hit
            if hit is not None:
                _registry().inc("nomad.engine.frames_reused")
                return hit
        # the SAME constraint code run on CPU over the packed host tensors
        masks = _host_signature_masks(t.attrs, t.elig, base_by_lane,
                                      con_by_lane, luts)
        if node_ok is not None:
            # the frame IS the static mask on the compact path: refuted
            # nodes leave the candidate set here
            masks = masks & node_ok[:t.n][None, :]
        width = len(con_by_lane)
        rows_l = [np.nonzero(masks[i])[0].astype(np.int32)
                  for i in range(width)]
        if self.mesh is None:
            nc = max(max((len(r) for r in rows_l), default=1), 1)
            nc = ((nc + 2047) // 2048) * 2048
            cand_rows = np.full((width, nc), npad, np.int32)
            cand_valid = np.zeros((width, nc), bool)
            for li, rows in enumerate(rows_l):
                cand_rows[li, :len(rows)] = rows
                cand_valid[li, :len(rows)] = True
        else:
            # per-shard frame blocks: shard s holds its slice of every
            # lane's candidates (global row ids; padding = npad is past
            # every shard's range)
            ndev = self._ndev
            nloc = npad // ndev
            shard_rows = [
                [rows[(rows // nloc) == sh] for rows in rows_l]
                for sh in range(ndev)]
            nc = max(max((len(r) for per in shard_rows
                          for r in per), default=1), 1)
            nc = ((nc + 511) // 512) * 512
            cand_rows = np.full((ndev, width, nc), npad, np.int32)
            cand_valid = np.zeros((ndev, width, nc), bool)
            for sh in range(ndev):
                for li, rows in enumerate(shard_rows[sh]):
                    cand_rows[sh, li, :len(rows)] = rows
                    cand_valid[sh, li, :len(rows)] = True
        _registry().inc("nomad.engine.frames_built")
        # shared from here on: a writer would corrupt every later wave
        cand_rows.setflags(write=False)
        cand_valid.setflags(write=False)
        out = (cand_rows, cand_valid,
               (jnp.asarray(cand_rows), jnp.asarray(cand_valid)))
        if node_ok is None:
            with self.packer.lock:
                if len(self._frame_cache) >= 4:
                    self._frame_cache.pop(next(iter(self._frame_cache)))
                self._frame_cache[key] = out
        return out

    def collect_batch(self, pending) -> List[Optional[BulkDecisions]]:
        """Blocking half of place_batch: fetch the packed buffer and
        expand per-item decisions."""
        if pending is None:
            return []
        if isinstance(pending, tuple):      # empty-cluster dispatch
            return [None] * len(pending[1])
        items = pending["items"]
        spans, counts, rs = (pending["spans"], pending["counts"],
                             pending["rs"])
        t, ctxs, n, npad = (pending["t"], pending["ctxs"],
                            pending["n"], pending["npad"])
        port_asks = pending.get("port_asks") or [()] * len(items)
        t1 = time.perf_counter_ns()
        buf_np = self._fetch(pending["buf"])
        if pending.get("perm") is not None:
            # laned schedule: reorder rows back to eval-major order so
            # the spans below slice each eval's contiguous rounds
            buf_np = buf_np[pending["perm"]]
        fill_k = pending.get("fill_k")

        def _full_fills():
            full = self._fetch(pending["fills_full"])
            if pending.get("perm") is not None:
                full = full[pending["perm"]]
            return full

        buf_np, slot_eff = _resolve_compact_fills(
            buf_np, _full_fills, fill_k or 0)
        rs_eff = slot_eff or rs

        dc_counts = self._dc_counts(t)
        elapsed = ((pending["prep_ns"] + time.perf_counter_ns() - t1)
                   // max(sum(counts), 1))
        decisions: List[Optional[BulkDecisions]] = []
        for gi, it in enumerate(items):
            lo, hi = spans[gi]
            if hi == lo:
                decisions.append(BulkDecisions(
                    tg_name=it.tg.name, picks=np.empty(0, np.int32),
                    node_ids=t.node_ids, round_size=rs, metrics=[],
                    nodes_evaluated=n))
                continue
            # a spread item's rounds hold one placement each
            irs = pending["item_rs"][gi]
            picks, meta = _unpack_rounds(
                buf_np[lo:hi], irs, counts[gi],
                slot_k=rs_eff if rs_eff != irs else 0)
            if npad != n:
                meta = meta.copy()
                meta[:, 7] -= npad - n
            metrics = self._metrics_from_meta(
                meta, n, int(ctxs[gi].pool_mask.sum()), dc_counts,
                t.node_ids, int(elapsed),
                port_dim=(port_collision_dimension([port_asks[gi]])
                          if port_asks[gi] else ""))
            decisions.append(BulkDecisions(
                tg_name=it.tg.name, picks=picks, node_ids=t.node_ids,
                round_size=irs, metrics=metrics, nodes_evaluated=n))
        return decisions

    def _no_nodes_decision(self, r: PlacementRequest, snapshot, job: Job
                           ) -> PlacementDecision:
        return PlacementDecision(
            tg_name=r.tg_name, node_id=None, score=0.0,
            metric=AllocMetric(nodes_evaluated=0))
