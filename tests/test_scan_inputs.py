"""The single-device scan's inputs as ONE buffer: `select.pack_scan_inputs`
lays an eval's per-eval fields and its usage replay out in one int32
array, the program `jit_place_packed` takes them back out by static
slices and bitcasts and adds the replay to the resident `used` ahead of
the scan.

Held bit for bit to the per-field form (every field an array of its own,
the replay scattered beforehand, as the engine launched the scan before):
the packed rows, the final `used` and `job_count`, and the replayed
`used` the launch hands back; in the XLA scan and in the fused kernel run
by Pallas' interpreter.  Then the engine: its resident `used` stays the
packer's host tensor through commits, stops and a replay too long to
fold, and one program serves every delta count."""

import copy

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nomad_tpu import mock
from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.ops import PlacementEngine, engine, scan_fused, select
from nomad_tpu.ops.select import SCAN_PACKED_FIELDS
from nomad_tpu.structs import RES_DIMS

from test_scan_fused import (described_inputs, fused_case, one_chip,  # noqa: F401
                             tpu_backend)
from test_scan_step import fleet, same_bits, scan_inputs, service, step_case
from test_spread_batched import harness, solo

REPLAY = 512


def two_groups():
    """A job of two groups with different asks, steps alternating."""
    job = service("si-2g", 10, affinity=True)
    tg = copy.deepcopy(job.task_groups[0])
    tg.name, tg.count = "second", 6
    tg.tasks[0].resources.cpu = 300
    job.task_groups.append(tg)
    inp = scan_inputs(fleet(60, 57), job, p=16, seed=2147940001)
    return inp._replace(tg_idx=jnp.asarray(np.arange(16, dtype=np.int32) % 2),
                        spread_algo=jnp.asarray(True))


def input_case(name: str):
    if name == "spread_distinct_property":
        return step_case(name)
    if name in ("static_ports", "padded_40_of_64"):
        return fused_case(name)[0]
    if name == "reschedule":
        return step_case(name)
    if name == "two_groups":
        return two_groups()
    raise AssertionError(name)


INPUT_CASES = ["spread_distinct_property", "static_ports", "padded_40_of_64",
               "reschedule", "two_groups"]


def usage_deltas(inp, kind: str):
    """(rows, values) pairs as the packer logs them: placements and stops
    of the group's ask on seeded nodes; `full` fills the replay section to
    its last row, `over` passes it."""
    rows_in = {"none": [], "some": [7, 20, 3], "full": [REPLAY - 12, 12],
               "over": [REPLAY, 40]}[kind]
    rng = np.random.default_rng(len(rows_in) + 40)
    n = inp.attrs.shape[0]
    req = np.asarray(inp.req)[0]
    out = []
    for j, k in enumerate(rows_in):
        sign = -1 if j == 1 else 1
        rows = rng.integers(0, n, k).astype(np.intp)
        out.append((rows, (sign * req[None, :]).repeat(k, 0).astype(np.int32)))
    return out


def both_forms(inp, kind: str):
    """(the per-field launch's inputs, the packed launch's resident
    inputs, its buffer, its layout, `used` after the replay)."""
    deltas = usage_deltas(inp, kind)
    # the resident copy lags the case's `used` by the deltas: the replay,
    # as the engine's scatter did it, brings it back
    replayed = np.asarray(inp.used0)
    resident = replayed.copy()
    for rows, vals in deltas:
        np.subtract.at(resident, rows, vals)
    fields = inp
    host = inp._replace(**{f: np.asarray(getattr(inp, f))
                           for f in SCAN_PACKED_FIELDS
                           if getattr(inp, f) is not None})
    if kind == "over":
        # past the section: the scatter first, the launch folds nothing
        resident, deltas = replayed, []
    layout, packed = select.pack_scan_inputs(host, deltas, REPLAY)
    resident_inp = inp._replace(used0=jnp.asarray(resident),
                                **dict.fromkeys(SCAN_PACKED_FIELDS))
    return fields, resident_inp, packed, layout, replayed


@pytest.mark.parametrize("deltas", ["none", "some", "full", "over"])
@pytest.mark.parametrize("case", INPUT_CASES)
def test_the_packed_launch_is_the_per_field_launch_bit_for_bit(case, deltas):
    inp = input_case(case)
    fields, resident_inp, packed, layout, replayed = both_forms(inp, deltas)
    # one buffer, the fields' words and the replay section, nothing else
    assert packed.dtype == np.int32 and packed.ndim == 1
    assert len(packed) == sum(int(np.prod(s)) for _, s, _ in layout)
    assert layout[-2:] == (("replay_rows", (REPLAY,), "int32"),
                           ("replay_vals", (REPLAY, RES_DIMS), "int32"))
    # the fields the scan body sees: the per-field form's, dtype for dtype
    unpacked, used0 = jax.jit(select.unpack_scan_inputs,
                              static_argnums=2)(resident_inp, packed, layout)
    for f in SCAN_PACKED_FIELDS + ("used0",):
        if getattr(fields, f) is not None:
            same_bits([getattr(fields, f)], [getattr(unpacked, f)])
    want = select.place_packed_jit(fields)
    got = select.place_packed_jit(resident_inp, packed, layout)
    assert len(got) == 4
    same_bits(want, got[:3])
    same_bits([replayed], [got[3]])
    if case == "static_ports":
        assert np.asarray(got[0]).shape[1] == 12 + RES_DIMS


@pytest.mark.parametrize("case", INPUT_CASES)
def test_the_fused_kernel_takes_the_packed_inputs_bit_for_bit(case):
    """The kernel behind the gate on the TPU, in Pallas' interpreter, on
    the fields the program unpacks: the per-field form's outputs."""
    inp = input_case(case)
    fields, resident_inp, packed, layout, replayed = both_forms(inp, "some")

    def packed_fused(inp, packed):
        inp, used0 = select.unpack_scan_inputs(inp, packed, layout)
        return (*scan_fused.place_packed_fused(inp, interpret=True), used0)

    want = jax.jit(lambda i: scan_fused.place_packed_fused(
        i, interpret=True))(fields)
    got = jax.jit(packed_fused)(resident_inp, packed)
    same_bits(want, got[:3])
    same_bits([replayed], [got[3]])


def test_a_field_jax_would_narrow_goes_up_narrowed():
    """A host field of 64 bits is taken as JAX takes it (int32, float32),
    and one that does not fit a word is refused."""
    inp = step_case("two_nodes")
    host = inp._replace(**{f: np.asarray(getattr(inp, f))
                           for f in SCAN_PACKED_FIELDS
                           if getattr(inp, f) is not None})
    wide = host._replace(desired=host.desired.astype(np.int64),
                         sp_weight=host.sp_weight.astype(np.float64))
    assert (select.scan_layout(wide, 8) == select.scan_layout(host, 8))
    same_bits([select.pack_scan_inputs(host, [], 8)[1]],
              [select.pack_scan_inputs(wide, [], 8)[1]])
    with pytest.raises(ValueError):
        select.scan_layout(host._replace(tg_idx=host.tg_idx.astype(np.int8)),
                           8)


# ------------------------------------------------------------ the engine

def replay_rows():
    labels = REGISTRY.counter_labels("nomad.engine.used_replay_rows")
    return {how: labels.get(f"how={how}", 0.0)
            for how in ("folded", "scatter")}


def test_the_resident_used_follows_the_host_through_folds_and_overflow():
    """Five solo evals on 40 nodes (the section: 512 rows) with commits,
    a stop of a job and 1,210 rows between them: after every launch the
    engine's resident `used` is the packer's host tensor at the version
    it names; rows that fit ride the launch, the overflow takes the
    scatter; every launch is packed, one program for all of them."""
    nodes = fleet(40, 58)
    h = harness(nodes)
    h.engine = eng = PlacementEngine(mesh=False)
    eng.packer.attach(h.state)
    assert engine._fold_rows(40) == REPLAY
    seen = []
    place = eng._place

    def checked(*a, **kw):
        out = place(*a, **kw)
        with eng.packer.lock:
            t = eng.packer._tensors
            seen.append((eng._used_version == t.used_version,
                         np.array_equal(np.asarray(eng._used_dev), t.used)))
        return out
    eng._place = checked

    inputs0 = REGISTRY.counter_labels("nomad.engine.scan_inputs")
    rows0 = replay_rows()
    moved = []

    def run(tag, count):
        before = replay_rows()
        job = service(f"si-{tag}", count)
        solo(h, job, tag)
        now = replay_rows()
        moved.append({k: now[k] - before[k] for k in now})
        return job

    job_a = run("a", 16)                      # no resident copy: uploaded
    size = select.place_packed_jit._cache_size()
    run("b", 12)                              # a's 16 rows ride the launch
    live = [a for a in h.state.snapshot().allocs_by_job(job_a.namespace,
                                                        job_a.id)]
    stops = []
    for a in live:
        a = a.copy()
        a.desired_status, a.client_status = "stop", "complete"
        stops.append(a)
    h.state.upsert_allocs(stops)
    run("c", 10)                              # b's 12 and a's 16 stops
    extra = [mock.alloc(node_id=nodes[i % 40].id) for i in range(600)]
    h.state.upsert_allocs(extra)
    for a in extra:
        a.desired_status, a.client_status = "stop", "complete"
    h.state.upsert_allocs(extra)
    run("d", 14)                              # 10 + 1,200: the scatter
    run("e", 11)                              # d's 14
    assert seen == [(True, True)] * 5
    assert moved == [{"folded": 0, "scatter": 0},
                     {"folded": 16, "scatter": 0},
                     {"folded": 28, "scatter": 0},
                     {"folded": 0, "scatter": 1210},
                     {"folded": 14, "scatter": 0}]
    assert replay_rows()["folded"] - rows0["folded"] == 58
    now = REGISTRY.counter_labels("nomad.engine.scan_inputs")
    assert now.get("form=packed", 0) - inputs0.get("form=packed", 0) == 5
    assert now.get("form=fields", 0) == inputs0.get("form=fields", 0)
    # five delta counts, one program (every eval pads to 16 steps)
    assert select.place_packed_jit._cache_size() == size
    assert ("scan", (40, 16)) in engine._KERNEL_SHAPES_SEEN
    for job in ("si-b", "si-c", "si-d", "si-e"):
        assert len(h.state.snapshot().allocs_by_job("default", job)) > 0


def test_folded_replays_race_alloc_events_and_each_other():
    """Three threads launch scans (each folding the deltas it finds and
    adopting what its launch replayed) while a fourth upserts and stops
    allocations, past the 256-entry replay window too, with the
    interpreter switching threads every 10 us: afterwards one more launch
    leaves the resident `used` the packer's host tensor exactly, so no
    delta was lost or applied twice."""
    import sys
    import threading

    from nomad_tpu.ops.engine import PlacementRequest
    nodes = fleet(24, 59)
    h = harness(nodes)
    eng = PlacementEngine(mesh=False)
    eng.packer.attach(h.state)
    job = service("si-race", 4)
    h.state.upsert_job(job)
    tg = job.task_groups[0]
    reqs = [PlacementRequest(tg_name=tg.name)] * 4
    eng.place(h.snapshot(), job, [tg], reqs, seed=1)      # compiles
    errors, stop = [], threading.Event()

    def writer():
        try:
            for i in range(300):
                a = mock.alloc(node_id=nodes[i % 24].id)
                h.state.upsert_allocs([a])
                if i % 3 == 0:
                    a = a.copy()
                    a.desired_status, a.client_status = "stop", "complete"
                    h.state.upsert_allocs([a])
        except Exception as e:  # pragma: no cover - fails below
            errors.append(e)

    def placer(k):
        try:
            while not stop.is_set():
                eng.place(h.snapshot(), job, [tg], reqs, seed=k)
        except Exception as e:  # pragma: no cover - fails below
            errors.append(e)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        placers = [threading.Thread(target=placer, args=(k,), daemon=True)
                   for k in (2, 3, 4)]
        w = threading.Thread(target=writer, daemon=True)
        for th in placers + [w]:
            th.start()
        w.join(120)
        stop.set()
        for th in placers:
            th.join(60)
    finally:
        sys.setswitchinterval(switch)
    assert not errors
    assert not w.is_alive() and not any(th.is_alive() for th in placers)
    eng.place(h.snapshot(), job, [tg], reqs, seed=5)
    with eng.packer.lock:
        t = eng.packer._tensors
        assert eng._used_version == t.used_version
        assert np.array_equal(np.asarray(eng._used_dev), t.used)
    assert replay_rows()["folded"] > 0


def test_the_packed_program_is_jit_place_packed():
    inp = step_case("two_nodes")
    _, resident_inp, packed, layout, _ = both_forms(inp, "some")
    text = select.place_packed_jit.lower(resident_inp, packed,
                                         layout).as_text()
    assert "jit_place_packed" in text.splitlines()[0]


@pytest.mark.parametrize("ports", [False, True])
def test_the_packed_program_compiles_for_a_v5e_as_one_custom_call(
        one_chip, monkeypatch, ports):  # noqa: F811
    """At spread5k's size, the program the engine launches there: the
    unpack and the replay as XLA ops ahead of the kernel's ONE custom
    call, the gate reading the TPU."""
    from jax.experimental.compilation_cache import compilation_cache
    n, p = 5000, 4096
    shaped = described_inputs(one_chip, n, p, ports)
    host = select.PlacementInputs(**{
        f: None if s is None else np.zeros(s.shape, s.dtype)
        for f, s in shaped._asdict().items()})
    layout = select.scan_layout(host, engine._fold_rows(n))
    words = sum(int(np.prod(s)) for _, s, _ in layout)
    resident_inp = shaped._replace(**dict.fromkeys(SCAN_PACKED_FIELDS))
    packed = jax.ShapeDtypeStruct((words,), jnp.int32, sharding=one_chip)
    tpu_backend(monkeypatch)
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(select.place_packed,
                           static_argnames="layout").lower(
            resident_inp, packed, layout).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "jit_place_packed" in text.splitlines()[0]
