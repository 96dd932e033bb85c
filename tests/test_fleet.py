"""`repro.serving.fleet`: the multi-tenant fleet layer.

The fleet contract under test:
  * a 3-net `FleetRouter` with staggered arrivals returns, per stream,
    logits bit-exact vs a lone batch-1 `StreamSession` of the same net;
  * pool sizes only come from the bucket ladder, every (net, rung) pool
    traces at most once ever — through grow AND shrink bounces;
  * autoscaling grows immediately on demand and shrinks only after
    `shrink_after` consecutive calm ticks (hysteresis);
  * a full admission FIFO raises `FleetQueueFull` (bounded backpressure);
  * threaded and synchronous host ingestion are bit-identical;
  * `serve_fleet` works from `DeployedProgram`s and from round-tripped
    ``.cutie`` `LoadedProgram`s (no graph objects on the load path).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import api, artifact
from repro.api.program import CutieProgram
from repro.serving import (
    FleetQueueFull,
    FleetRouter,
    FrameFeeder,
    NetBucket,
    ScaleEvent,
    StreamRequest,
    bucket_ladder,
    serve_fleet,
)

NET_SPECS = {
    # three deliberately distinct shapes: channel widths, ring depths and
    # class counts all differ, so a cross-net routing mixup cannot alias
    "tiny_a": dict(input_ch=2, width=4, tcn_steps=4, n_classes=3),
    "tiny_b": dict(input_ch=3, width=6, tcn_steps=3, n_classes=4),
    "tiny_c": dict(input_ch=2, width=5, tcn_steps=5, n_classes=2),
}


def tiny_net(name, *, input_ch, width, tcn_steps, n_classes):
    return api.CutieGraph(
        name=name, input_hw=(4, 4), input_ch=input_ch, n_classes=n_classes,
        tcn_steps=tcn_steps,
        layers=(api.conv2d(input_ch, width), api.global_pool(),
                api.tcn(width, width, dilation=1),
                api.tcn(width, width, dilation=2),
                api.last_step(), api.fc(width, n_classes)),
    )


def clips_for(graph, n_streams, frames, seed=0):
    shape = (n_streams, frames, *graph.input_hw, graph.input_ch)
    return (jax.random.uniform(jax.random.PRNGKey(seed), shape) < 0.3
            ).astype(jnp.float32)


@pytest.fixture(scope="module")
def fleet_programs():
    """{name: DeployedProgram} for the three tiny temporal nets."""
    out = {}
    for i, (name, spec) in enumerate(NET_SPECS.items()):
        prog = CutieProgram(tiny_net(name, **spec))
        calib = clips_for(prog.graph, 2, 4, seed=100 + i)
        out[name] = prog.quantize(
            prog.init(jax.random.PRNGKey(i)), calib=calib)
    return out


def exact(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def lone_logits(deployed, clip, backend="ref"):
    """Final logits of one clip through an independent batch-1 session."""
    session = deployed.stream(batch=1, backend=backend)
    for t in range(clip.shape[0]):
        out = session.step(clip[t][None])
    return np.asarray(out)[0]


# ---------------------------------------------------------------------------
# the ladder
# ---------------------------------------------------------------------------

class TestBucketLadder:
    def test_powers_of_two_up_to_cap(self):
        assert bucket_ladder(1) == (1,)
        assert bucket_ladder(8) == (1, 2, 4, 8)
        assert bucket_ladder(16) == (1, 2, 4, 8, 16)

    def test_non_pow2_cap_is_last_rung(self):
        assert bucket_ladder(12) == (1, 2, 4, 8, 12)
        assert bucket_ladder(3) == (1, 2, 3)

    def test_base_offsets_ladder(self):
        assert bucket_ladder(16, base=4) == (4, 8, 16)
        assert bucket_ladder(6, base=2) == (2, 4, 6)

    @pytest.mark.parametrize("cap,base", [(0, 1), (4, 0), (2, 4)])
    def test_rejects_bad_bounds(self, cap, base):
        with pytest.raises(ValueError, match="cap >= base >= 1"):
            bucket_ladder(cap, base=base)


# ---------------------------------------------------------------------------
# NetBucket: admission bound + autoscale hysteresis
# ---------------------------------------------------------------------------

class TestNetBucket:
    def test_rejects_non_temporal_program(self):
        g = api.CutieGraph(
            name="tiny_spatial", input_hw=(4, 4), input_ch=2, n_classes=3,
            layers=(api.conv2d(2, 4), api.global_pool(), api.fc(4, 3)),
        )
        prog = CutieProgram(g)
        dep = prog.quantize(prog.init(jax.random.PRNGKey(0)))
        with pytest.raises(ValueError, match="not temporal"):
            NetBucket("spatial", dep, backend="ref", ladder=(1, 2))

    def test_rejects_unsorted_ladder(self, fleet_programs):
        dep = fleet_programs["tiny_a"]
        with pytest.raises(ValueError, match="ascending"):
            NetBucket("a", dep, backend="ref", ladder=(4, 2, 1))
        with pytest.raises(ValueError, match="must be >= 1"):
            NetBucket("a", dep, backend="ref", ladder=(1, 2), queue_limit=0)

    def test_bounded_fifo_raises_fleet_queue_full(self, fleet_programs):
        """Pre-tick submits all land in the FIFO (admission happens at
        tick), so with queue_limit=2 the third submit is the overflow."""
        dep = fleet_programs["tiny_a"]
        frames = clips_for(dep.graph, 4, 3, seed=30)
        bucket = NetBucket("tiny_a", dep, backend="ref", ladder=(1,),
                           queue_limit=2, ingest="sync")
        bucket.submit(StreamRequest("s0", frames[0]))
        bucket.submit(StreamRequest("s1", frames[1]))
        with pytest.raises(FleetQueueFull, match="admission FIFO full"):
            bucket.submit(StreamRequest("s2", frames[2]))
        bucket.tick()            # s0 admitted, s1 queued -> FIFO has room
        bucket.submit(StreamRequest("s3", frames[3]))
        results = bucket.batcher
        while bucket.pending:
            bucket.tick()
        assert {r.stream_id for r in results.results} == {"s0", "s1", "s3"}
        bucket.close()

    def test_autoscale_grow_then_shrink_with_hysteresis(self, fleet_programs):
        """Demand 4 grows 1->4 in one decision (rung_for, not one rung per
        tick); shrink waits `shrink_after` consecutive calm ticks and a
        single busy tick resets the calm counter."""
        dep = fleet_programs["tiny_a"]
        frames = clips_for(dep.graph, 5, 8, seed=31)
        bucket = NetBucket("tiny_a", dep, backend="ref", ladder=(1, 2, 4),
                           shrink_after=2, ingest="sync")
        for i in range(4):
            bucket.submit(StreamRequest(f"s{i}", frames[i]))
        assert bucket.size == 1
        bucket.tick()
        assert bucket.size == 4           # grew straight to the fitting rung
        grow = bucket.scale_events[0]
        assert isinstance(grow, ScaleEvent)
        assert (grow.reason, grow.from_size, grow.to_size, grow.demand) == \
            ("grow", 1, 4, 4)
        # drain: all four streams finish the 8-frame clips in lockstep, so
        # demand collapses 4 -> 0 at once; calm ticks then accumulate
        while bucket.batcher.inflight_count:
            bucket.tick()
        assert bucket.size == 4           # no shrink yet (calm not reached)
        bucket.tick()                     # calm tick 1 of 2
        assert bucket.size == 4
        bucket.tick()                     # calm tick 2 of 2 -> shrink
        assert bucket.size == 1
        shrink = bucket.scale_events[-1]
        assert shrink.reason == "shrink" and shrink.to_size == 1
        # hysteresis: one calm tick then fresh demand must NOT shrink later
        bucket.submit(StreamRequest("late", frames[4]))
        bucket.tick()
        assert bucket._calm_ticks == 0
        # the zero-retrace audit: every rung visited traced exactly once
        assert {s: p.trace_count for s, p in bucket.pools.items()} == \
            {1: 1, 4: 1}
        bucket.close()

    def test_regrow_reuses_cached_pool_without_retrace(self, fleet_programs):
        """Bounce 1 -> 2 -> 1 -> 2: the second grow must reuse the cached
        rung-2 pool (trace_count stays 1)."""
        dep = fleet_programs["tiny_b"]
        frames = clips_for(dep.graph, 4, 4, seed=32)
        bucket = NetBucket("tiny_b", dep, backend="ref", ladder=(1, 2),
                           shrink_after=1, ingest="sync")
        for wave in range(2):
            for i in range(2):
                bucket.submit(
                    StreamRequest(f"w{wave}s{i}", frames[2 * wave + i]))
            while bucket.pending:
                bucket.tick()
            bucket.tick()  # calm tick -> shrink back to 1
            assert bucket.size == 1
        reasons = [e.reason for e in bucket.scale_events]
        assert reasons == ["grow", "shrink", "grow", "shrink"]
        # rung 1 never steps a frame (work happens at rung 2), so it never
        # traces at all; rung 2 traces exactly once across both waves
        assert {s: p.trace_count for s, p in bucket.pools.items()} == \
            {1: 0, 2: 1}
        assert len(bucket.pools) == 2     # rungs cached, not rebuilt
        bucket.close()


# ---------------------------------------------------------------------------
# FleetRouter: routing + multi-net exactness
# ---------------------------------------------------------------------------

class TestFleetRouter:
    def test_routing_errors(self, fleet_programs):
        router = FleetRouter(backend="ref", max_pool_size=2, ingest="sync")
        clip = clips_for(fleet_programs["tiny_a"].graph, 1, 2)[0]
        with pytest.raises(KeyError, match="no nets registered"):
            router.submit(StreamRequest("x", clip))
        router.register("tiny_a", fleet_programs["tiny_a"])
        router.register("tiny_b", fleet_programs["tiny_b"])
        with pytest.raises(ValueError, match="already registered"):
            router.register("tiny_a", fleet_programs["tiny_a"])
        with pytest.raises(KeyError, match="unknown net 'nope'"):
            router.submit(StreamRequest("x", clip, net="nope"))
        with pytest.raises(KeyError, match="set StreamRequest.net"):
            router.submit(StreamRequest("x", clip))     # ambiguous: 2 nets
        router.close()

    def test_single_bucket_accepts_untagged_requests(self, fleet_programs):
        dep = fleet_programs["tiny_a"]
        with FleetRouter(backend="ref", max_pool_size=2,
                         ingest="sync") as router:
            router.register("tiny_a", dep)
            clip = clips_for(dep.graph, 1, 3, seed=40)[0]
            router.submit(StreamRequest("cam", clip))   # net=None -> only net
            results = router.run()
        assert results[0].net == "tiny_a"
        exact(results[0].logits, lone_logits(dep, clip))

    def test_three_net_fleet_staggered_is_bit_exact(self, fleet_programs):
        """The fleet-smoke contract in miniature: 3 nets x 4 streams with
        interleaved arrivals, pooled logits bit-exact vs lone sessions,
        zero retrace on every rung of every bucket."""
        streams, frames = 4, 5
        router = serve_fleet(fleet_programs, backend="ref",
                             max_pool_size=2, ingest="sync")
        clips = {name: clips_for(dep.graph, streams, frames, seed=50 + i)
                 for i, (name, dep) in enumerate(fleet_programs.items())}
        for i, name in enumerate(fleet_programs):
            for s in range(streams):
                router.submit(StreamRequest(
                    f"{name}/cam{s}", clips[name][s], net=name,
                    arrival=i + s * len(fleet_programs)))
        results = router.run()
        assert len(results) == streams * len(fleet_programs)
        for r in results:
            sid = int(r.stream_id.rsplit("cam", 1)[1])
            exact(r.logits,
                  lone_logits(fleet_programs[r.net], clips[r.net][sid]))
        stats = router.stats()
        assert stats["aggregate"]["nets"] == 3
        assert stats["aggregate"]["completed"] == 12
        for name, s in stats["nets"].items():
            assert all(tc == 1 for tc in s["pools_traced"].values()), \
                f"{name} retraced: {s['pools_traced']}"
            assert s["latency_ms_p50"] > 0.0
            assert set(s["latency_by_pool_size"]) <= set(s["ladder"])
        router.close()

    @pytest.mark.parametrize("modes", [("thread", "sync")])
    def test_threaded_and_sync_ingestion_bit_identical(
        self, fleet_programs, modes
    ):
        """The feeder-thread pipelining must be invisible to numerics:
        the identical workload through ingest=thread and ingest=sync
        routers yields byte-identical logits for every stream."""
        per_mode = {}
        for mode in modes:
            router = serve_fleet(fleet_programs, backend="ref",
                                 max_pool_size=2, ingest=mode)
            for i, (name, dep) in enumerate(fleet_programs.items()):
                clips = clips_for(dep.graph, 3, 4, seed=60 + i)
                for s in range(3):
                    router.submit(StreamRequest(
                        f"{name}/s{s}", clips[s], net=name, arrival=s))
            results = router.run()
            per_mode[mode] = {r.stream_id: np.asarray(r.logits)
                              for r in results}
            threaded = {n: s["ingest_threaded"]
                        for n, s in router.stats()["nets"].items()}
            if mode == "sync":
                assert not any(threaded.values())
            router.close()
        a, b = (per_mode[m] for m in modes)
        assert a.keys() == b.keys()
        for sid in a:
            exact(a[sid], b[sid])

    def test_queue_limit_propagates_and_overrides(self, fleet_programs):
        dep = fleet_programs["tiny_c"]
        router = FleetRouter(backend="ref", max_pool_size=1, queue_limit=1,
                             ingest="sync")
        router.register("tiny_c", dep)
        router.register("roomy", fleet_programs["tiny_a"], queue_limit=8)
        assert router.buckets["tiny_c"].queue_limit == 1
        assert router.buckets["roomy"].queue_limit == 8
        clip = clips_for(dep.graph, 2, 2, seed=70)
        router.submit(StreamRequest("a", clip[0], net="tiny_c"))
        with pytest.raises(FleetQueueFull):
            router.submit(StreamRequest("b", clip[1], net="tiny_c"))
        router.close()


# ---------------------------------------------------------------------------
# serve_fleet entry points: DeployedProgram and .cutie LoadedProgram
# ---------------------------------------------------------------------------

class TestServeFleetEntryPoints:
    def test_deployed_program_serve_fleet(self, fleet_programs):
        dep = fleet_programs["tiny_a"]
        with dep.serve_fleet(backend="ref", max_pool_size=2,
                             ingest="sync") as router:
            assert set(router.buckets) == {"tiny_a"}
            clip = clips_for(dep.graph, 1, 3, seed=80)[0]
            router.submit(StreamRequest("cam", clip))
            (result,) = router.run()
        exact(result.logits, lone_logits(dep, clip))

    def test_loaded_cutie_program_serve_fleet(self, fleet_programs):
        """Fleet serving straight from artifact bytes: no graph objects,
        bitsim backend, still bit-exact vs the deployed original."""
        dep = fleet_programs["tiny_b"]
        loaded = artifact.loads(dep.to_artifact_bytes())
        with loaded.serve_fleet(backend="bitsim", max_pool_size=2,
                                ingest="sync") as router:
            bucket = router.buckets["tiny_b"]
            assert bucket.backend == "bitsim"
            clips = clips_for(dep.graph, 2, 3, seed=81)
            for s in range(2):
                router.submit(StreamRequest(f"s{s}", clips[s]))
            results = {r.stream_id: r for r in router.run()}
        for s in range(2):
            exact(results[f"s{s}"].logits,
                  lone_logits(dep, clips[s], backend="ref"))

    def test_mixed_deployed_and_loaded_fleet(self, fleet_programs):
        dep_a = fleet_programs["tiny_a"]
        loaded_c = artifact.loads(
            fleet_programs["tiny_c"].to_artifact_bytes())
        router = FleetRouter(backend="ref", max_pool_size=2, ingest="sync")
        router.register("tiny_a", dep_a)
        router.register("tiny_c", loaded_c, backend="bitsim")
        clip_a = clips_for(dep_a.graph, 1, 3, seed=82)[0]
        clip_c = clips_for(fleet_programs["tiny_c"].graph, 1, 3, seed=83)[0]
        router.submit(StreamRequest("a0", clip_a, net="tiny_a"))
        router.submit(StreamRequest("c0", clip_c, net="tiny_c"))
        results = {r.stream_id: r for r in router.run()}
        exact(results["a0"].logits, lone_logits(dep_a, clip_a))
        exact(results["c0"].logits,
              lone_logits(fleet_programs["tiny_c"], clip_c))
        router.close()


# ---------------------------------------------------------------------------
# FrameFeeder: the double-buffer prefetch unit
# ---------------------------------------------------------------------------

class TestFrameFeeder:
    SHAPE = (2, 2, 1)

    def _items(self, n, base=0.0):
        return [(f"s{i}", i,
                 np.full((1, *self.SHAPE), base + i, np.float32), 0)
                for i in range(n)]

    @pytest.mark.parametrize("mode", ["thread", "sync"])
    def test_prefetch_take_round_trip(self, mode):
        feeder = FrameFeeder(mode=mode)
        assert feeder.take() is None                  # nothing outstanding
        feeder.prefetch(4, self.SHAPE, self._items(3))
        batch, active, covered = feeder.take()
        assert batch.shape == (4, *self.SHAPE) and batch.dtype == np.float32
        assert covered == {"s0": 0, "s1": 1, "s2": 2}
        assert list(active) == [True, True, True, False]
        for i in range(3):
            assert (batch[i] == float(i)).all()
        assert (batch[3] == 0.0).all()                # uncovered lane zeroed
        assert feeder.take() is None                  # consumed
        feeder.close()

    def test_double_buffers_alternate_per_prefetch(self):
        feeder = FrameFeeder(mode="sync")
        feeder.prefetch(2, self.SHAPE, self._items(1, base=5.0))
        first, _, _ = feeder.take()
        feeder.prefetch(2, self.SHAPE, self._items(1, base=9.0))
        second, _, _ = feeder.take()
        assert first is not second                    # back buffer flipped
        assert (first[0] == 5.0).all()                # ...so 1st is untouched
        assert (second[0] == 9.0).all()
        feeder.prefetch(2, self.SHAPE, self._items(1, base=7.0))
        third, _, _ = feeder.take()
        assert third is first                         # pair of two, reused
        feeder.close()

    def test_invalidate_discards_pending_prefetch(self):
        feeder = FrameFeeder(mode="thread")
        feeder.prefetch(2, self.SHAPE, self._items(2))
        feeder.invalidate()
        assert feeder.take() is None
        feeder.close()

    def test_sync_mode_never_threads(self):
        feeder = FrameFeeder(mode="sync")
        assert not feeder.threaded
        feeder.close()

    def test_rejects_unknown_mode(self):
        with pytest.raises(ValueError, match="unknown ingest mode"):
            FrameFeeder(mode="eager")
