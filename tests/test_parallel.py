"""Sharded placement tests on the 8-device virtual CPU mesh.

Verifies the two-stage top-k / psum'd count-state design produces the SAME
decisions as the single-device kernel."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nomad_tpu import mock
from nomad_tpu.ops import PlacementRequest
from nomad_tpu.ops.select import PlacementInputs, place_jit
from nomad_tpu.pack import ClusterPacker, lower_spreads
from nomad_tpu.parallel import make_mesh, pad_nodes, place_sharded_fn
from nomad_tpu.scheduler import Harness
from nomad_tpu.structs import Constraint, Spread, SpreadTarget


def build_inputs(n_nodes=16, count=12, spread=True, pad_to=None):
    h = Harness()
    for i in range(n_nodes):
        n = mock.node(datacenter=f"dc{i % 3 + 1}")
        n.meta = {"rack": f"r{i % 4}"}
        h.state.upsert_node(n)
    job = mock.job()
    job.datacenters = ["dc1", "dc2", "dc3"]
    if spread:
        job.spreads = [Spread(attribute="${node.datacenter}", weight=100,
                              targets=(SpreadTarget("dc1", 50),
                                       SpreadTarget("dc2", 30),
                                       SpreadTarget("dc3", 20)))]
    job.constraints.append(Constraint("${meta.rack}", "distinct_property", "99"))
    job.task_groups[0].count = count
    h.state.upsert_job(job)
    snap = h.snapshot()

    packer = ClusterPacker()
    t = packer.build(snap)
    tgt = packer.lower_task_groups(job, job.task_groups)
    ctx = packer.job_context(job, snap, t)
    sp = lower_spreads(packer, job, t, snap)
    pd = packer.lower_distinct(job, job.task_groups, tgt, t, snap)

    n = t.n
    n_pad = pad_to or n
    def padn(a, fill=0):
        if a.shape[0] == n_pad:
            return a
        pad = np.full((n_pad - a.shape[0],) + a.shape[1:], fill, a.dtype)
        return np.concatenate([a, pad], axis=0)
    def padcols(a, fill):
        if a.shape[1] == n_pad:
            return a
        pad = np.full(a.shape[:1] + (n_pad - a.shape[1],), fill, a.dtype)
        return np.concatenate([a, pad], axis=1)

    p = count
    inp = PlacementInputs(
        attrs=jnp.asarray(padn(t.attrs, -1)),
        cap=jnp.asarray(padn(t.cap)),
        used0=jnp.asarray(padn(t.used)),
        elig=jnp.asarray(padn(t.elig.astype(bool), False)),
        dc_mask=jnp.asarray(padn(ctx.dc_mask, False)),
        pool_mask=jnp.asarray(padn(ctx.pool_mask, False)),
        luts=jnp.asarray(tgt.luts),
        con=jnp.asarray(tgt.con),
        aff=jnp.asarray(tgt.aff),
        req=jnp.asarray(tgt.req),
        desired=jnp.asarray(np.array([tg.count for tg in job.task_groups],
                                     np.int32)),
        dh_limit=jnp.asarray(tgt.dh_limit),
        sp_nodeval=jnp.asarray(padcols(sp.sp_nodeval, -1)),
        sp_weight=jnp.asarray(sp.sp_weight),
        sp_expected=jnp.asarray(sp.sp_expected),
        sp_counts0=jnp.asarray(sp.sp_counts0),
        pd_nodeval=jnp.asarray(padcols(pd.pd_nodeval, -1)),
        pd_limit=jnp.asarray(pd.pd_limit),
        pd_apply=jnp.asarray(pd.pd_apply),
        pd_counts0=jnp.asarray(pd.pd_counts0),
        tg_idx=jnp.zeros(p, jnp.int32),
        prev_row=jnp.full(p, -1, jnp.int32),
        active=jnp.ones(p, bool),
        job_count0=jnp.asarray(padn(ctx.job_count)),
        spread_algo=jnp.asarray(False),
    )
    return h, t, inp


class TestShardedPlacement:
    def test_eight_devices_available(self):
        assert len(jax.devices()) >= 8

    def test_sharded_matches_single_device(self):
        mesh = make_mesh(8)
        n_pad = pad_nodes(16, 8)
        h, t, inp = build_inputs(n_nodes=16, count=12, pad_to=n_pad)
        single = place_jit(inp)
        sharded = place_sharded_fn(mesh)(inp)
        assert (np.asarray(single.picks) >= 0).all()   # non-trivial scenario
        np.testing.assert_array_equal(np.asarray(single.picks),
                                      np.asarray(sharded.picks))
        np.testing.assert_allclose(np.asarray(single.scores),
                                   np.asarray(sharded.scores), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(single.n_feasible),
                                      np.asarray(sharded.n_feasible))
        np.testing.assert_array_equal(np.asarray(single.n_filtered),
                                      np.asarray(sharded.n_filtered))
        # final usage: sharded output is globally identical once gathered
        np.testing.assert_array_equal(np.asarray(single.used),
                                      np.asarray(sharded.used))

    def test_padded_spread_batch_matches_on_the_real_rows(self):
        """A batch padded as the engine pads it (12 of 16 steps active),
        under a spread stanza and a distinct_property: the single-device
        loop stops at its trip count, the sharded scan runs its padding
        masked, and the real rows agree node for node and metric for
        metric, as does the final state."""
        mesh = make_mesh(8)
        h, t, inp = build_inputs(n_nodes=16, count=12,
                                 pad_to=pad_nodes(16, 8))
        inp = inp._replace(
            tg_idx=jnp.zeros(16, jnp.int32),
            prev_row=jnp.full(16, -1, jnp.int32),
            active=jnp.arange(16) < 12)
        single = place_jit(inp)
        sharded = place_sharded_fn(mesh)(inp)
        assert (np.asarray(single.picks)[:12] >= 0).all()
        for name in ("picks", "topk_rows", "n_feasible", "n_filtered",
                     "n_exhausted", "dim_exhausted"):
            np.testing.assert_array_equal(
                np.asarray(getattr(single, name))[:12],
                np.asarray(getattr(sharded, name))[:12], err_msg=name)
        for name in ("scores", "topk_scores"):
            np.testing.assert_allclose(
                np.asarray(getattr(single, name))[:12],
                np.asarray(getattr(sharded, name))[:12], atol=1e-5,
                err_msg=name)
        for name in ("used", "job_count"):
            np.testing.assert_array_equal(np.asarray(getattr(single, name)),
                                          np.asarray(getattr(sharded, name)))
        # past the real rows neither placed; only the sharded scan ran them
        assert (np.asarray(single.picks)[12:] == -1).all()
        assert (np.asarray(sharded.picks)[12:] == -1).all()
        assert not np.asarray(single.n_feasible)[12:].any()
        assert np.asarray(sharded.n_feasible)[12:].all()

    def test_sharded_spread_distribution(self):
        mesh = make_mesh(8)
        n_pad = pad_nodes(12, 8)
        h, t, inp = build_inputs(n_nodes=12, count=10, pad_to=n_pad)
        out = place_sharded_fn(mesh)(inp)
        picks = np.asarray(out.picks)
        assert (picks >= 0).all()
        dcs = {}
        snap = h.snapshot()
        for row in picks:
            dc = snap.node_by_id(t.node_ids[int(row)]).datacenter
            dcs[dc] = dcs.get(dc, 0) + 1
        assert dcs == {"dc1": 5, "dc2": 3, "dc3": 2}

    def test_padding_rows_never_picked(self):
        mesh = make_mesh(8)
        h, t, inp = build_inputs(n_nodes=10, count=8, pad_to=16)
        out = place_sharded_fn(mesh)(inp)
        picks = np.asarray(out.picks)
        assert (picks < 10).all()   # rows 10..15 are padding
