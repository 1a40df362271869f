"""The exact scan as ONE Pallas kernel (ISSUE 39): `scan_fused.
place_packed_fused`, run in Pallas' interpreter on the CPU, is
`select.place_packed` bit for bit (the buffer, the final `used` and
`job_count`), and `scan_gate` sends every launch it cannot take to the
XLA scan, counted in `nomad.engine.scan_launches{impl, why}`.

On the CPU `select.place_packed` IS the XLA scan (the gate's `backend`),
so the oracle below is the program the tier-1 suite has always held to
`test_scan_step`'s reference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.ops import PlacementEngine, scan_fused, select

from test_scan_step import (STEP_CASES, fleet, padded_case, same_bits,
                            scan_inputs, service, step_case)
from test_spread_batched import harness, solo


def interpreted(inp):
    return scan_fused.place_packed_fused(inp, interpret=True)


fused_jit = jax.jit(interpreted)


def with_ports(inp, seed: int):
    """Static-port state on `inp`: three values, each held by about a
    fifth of the nodes, the group asking the first and the third."""
    rng = np.random.default_rng(seed)
    n = inp.attrs.shape[0]
    g = inp.req.shape[0]
    ask = np.zeros((g, 3), bool)
    ask[:, [0, 2]] = True
    return inp._replace(pt_taken0=jnp.asarray(rng.random((3, n)) < 0.2),
                        pt_ask=jnp.asarray(ask))


def fused_case(name: str):
    """(inputs, the rows a step wrote: all of them but a padded tail)."""
    if name in STEP_CASES:
        inp = step_case(name)
        return inp, inp.tg_idx.shape[0]
    if name == "padded_40_of_64":
        inp = scan_inputs(fleet(70, 51), service("fz-p", 40), p=64,
                          seed=2147937003)
        active = np.arange(64) < 40
        active[[5, 17]] = False           # holes inside the trip count
        return inp._replace(active=jnp.asarray(active)), 40
    if name == "hole":
        inp, n_run = padded_case("hole")
        return inp, n_run
    if name == "static_ports":
        inp = with_ports(scan_inputs(fleet(90, 52), service("fz-s", 30),
                                     seed=11), 52)
        return inp, inp.tg_idx.shape[0]
    if name == "static_ports_distinct_property":
        inp = step_case("spread_distinct_property")
        return with_ports(inp, 53), inp.tg_idx.shape[0]
    if name == "nodes_1100":
        # more than one (8, 128) tile of nodes, the last one part-filled
        inp = scan_inputs(fleet(1100, 54), service("fz-n", 24, affinity=True),
                          seed=987654321)
        return inp, inp.tg_idx.shape[0]
    raise AssertionError(name)


FUSED_CASES = STEP_CASES + ["padded_40_of_64", "hole", "static_ports",
                            "static_ports_distinct_property", "nodes_1100"]


@pytest.mark.parametrize("case", FUSED_CASES)
def test_the_fused_kernel_is_the_xla_scan_bit_for_bit(case):
    inp, n_run = fused_case(case)
    want = [np.asarray(x) for x in select.place_packed_jit(inp)]
    got = [np.asarray(x) for x in fused_jit(inp)]
    same_bits(want, got)
    picks = got[0][:n_run, 0]
    active = np.asarray(inp.active)[:n_run]
    if case in ("seed_0", "live_seed", "padded_40_of_64", "nodes_1100"):
        assert (picks[active] >= 0).all()
    if case.startswith("static_ports"):
        # the port column counts the nodes a step lost to a held value
        assert got[0].shape[1] == 12 + inp.cap.shape[1]
        assert got[0][:n_run, -1].any()


def tpu_backend(monkeypatch):
    """The gate as it reads on a TPU host (the test steers it; the
    program has no such setting)."""
    monkeypatch.setattr(scan_fused.jax, "default_backend", lambda: "tpu")


def test_the_gate_takes_xla_off_the_tpu_over_the_budget_and_past_the_chains(
        monkeypatch):
    inp = step_case("spread_distinct_property")
    assert scan_fused.scan_gate(inp) == ("xla", "backend")
    tpu_backend(monkeypatch)
    assert scan_fused.scan_gate(inp) == ("fused", "fits")
    # the budget is the resident bytes the shapes give
    need = scan_fused.resident_bytes(inp)
    monkeypatch.setattr(scan_fused, "VMEM_BUDGET", need)
    assert scan_fused.scan_gate(inp) == ("fused", "fits")
    monkeypatch.setattr(scan_fused, "VMEM_BUDGET", need - 1)
    assert scan_fused.scan_gate(inp) == ("xla", "vmem")
    monkeypatch.undo()
    tpu_backend(monkeypatch)
    wide = scan_fused.MAX_VALUES + 1
    assert scan_fused.scan_gate(inp._replace(
        sp_counts0=jnp.zeros((1, wide), jnp.float32))) == ("xla", "values")
    assert scan_fused.scan_gate(inp._replace(
        pd_counts0=jnp.zeros((1, wide), jnp.int32))) == ("xla", "values")
    # node state grows the resident bytes: a large fleet is over budget
    big = scan_fused.VMEM_BUDGET // (4 * 40) + 1
    assert scan_fused.resident_bytes(inp._replace(
        attrs=jnp.zeros((big, 1), jnp.int32))) > scan_fused.VMEM_BUDGET


def test_every_engine_scan_launch_counts_under_the_gates_why(monkeypatch):
    """Three solo spread evals through the engine, each one launch of the
    single-device scan: on the CPU (`backend`), on a TPU-reading gate
    with the budget at zero (`vmem`) and with the value chains at one
    (`values`); every one runs the XLA scan and places its job."""
    nodes = fleet(40, 55)
    before = REGISTRY.counter_labels("nomad.engine.scan_launches")
    placed = []
    for tag, steer in (("backend", None), ("vmem", "VMEM_BUDGET"),
                       ("values", "MAX_VALUES")):
        if steer:
            tpu_backend(monkeypatch)
            monkeypatch.setattr(scan_fused, steer, 0 if tag == "vmem" else 1)
        h = harness(nodes)
        h.engine = PlacementEngine(mesh=False)    # the single-device scan
        h.engine.packer.attach(h.state)
        job = service(f"gate-{tag}", 6)
        solo(h, job, tag)
        placed.append(len(h.state.snapshot().allocs_by_job(job.namespace,
                                                            job.id)))
        monkeypatch.undo()
    now = REGISTRY.counter_labels("nomad.engine.scan_launches")
    moved = {k: now.get(k, 0.0) - before.get(k, 0.0) for k in now}
    assert {k: v for k, v in moved.items() if v} == {
        "impl=xla,why=backend": 1, "impl=xla,why=vmem": 1,
        "impl=xla,why=values": 1}
    assert placed == [6, 6, 6]


# ------------------------------------------- compiled for a described v5e

@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described (not attached) v5e: Mosaic compiles the
    kernel here as it would on the chip."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def described_inputs(sharding, n: int, p: int, ports: bool):
    """spread5k's shapes (one group, one spread of three values, one inert
    distinct_property row) at `n` nodes and `p` steps, as shapes only."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_
    return select.PlacementInputs(
        attrs=s((n, 12), i32), cap=s((n, 4), i32), used0=s((n, 4), i32),
        elig=s((n,), b), dc_mask=s((n,), b), pool_mask=s((n,), b),
        luts=s((2, 64), b), con=s((1, 2, 3), i32), aff=s((1, 1, 4), i32),
        req=s((1, 4), i32), desired=s((1,), i32), dh_limit=s((1,), i32),
        sp_nodeval=s((1, n), i32), sp_weight=s((1,), f32),
        sp_expected=s((1, 3), f32), sp_counts0=s((1, 3), f32),
        pd_nodeval=s((1, n), i32), pd_limit=s((1,), i32),
        pd_apply=s((1, 1), b), pd_counts0=s((1, 1), i32),
        tg_idx=s((p,), i32), prev_row=s((p,), i32), active=s((p,), b),
        job_count0=s((n,), i32), spread_algo=s((), b), seed=s((), jnp.uint32),
        pt_taken0=s((2, n), b) if ports else None,
        pt_ask=s((1, 2), b) if ports else None)


@pytest.mark.parametrize("n,p,ports", [(5000, 4096, False),
                                       (5000, 4096, True),
                                       (50000, 512, False)])
def test_the_kernel_compiles_for_a_v5e_within_its_budget(one_chip, n, p,
                                                         ports):
    """What interpret mode cannot show: Mosaic takes the kernel at
    spread5k's size (with static ports too) and at 50,000 nodes, each
    within the gate's VMEM budget, as one custom call inside
    `jit_place_packed`."""
    from jax.experimental.compilation_cache import compilation_cache
    inp = described_inputs(one_chip, n, p, ports)
    assert scan_fused.resident_bytes(inp) <= scan_fused.VMEM_BUDGET
    # a TPU executable can be written to the persistent cache here but
    # not read back without a chip: keep it out of the cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        def place_packed(inp):
            return scan_fused.place_packed_fused(inp)
        compiled = jax.jit(place_packed).lower(inp).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    assert compiled.as_text().count("tpu_custom_call") == 1
