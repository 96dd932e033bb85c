"""Activity-gated serving: the differential gating contract.

The contract under test (`repro.serving.gating`):
  * the set of frames a gated `ContinuousBatcher` processes is EXACTLY
    what `ActivityGate.plan` computes from the activity trace — pure
    function of the trace, independent of slot contention, park/wake/
    evict/refill churn, or arrival staggering;
  * a gated stream's logits are bit-exact vs a lone batch-1
    `StreamSession` fed exactly the plan-selected frames, on the fused
    AND ref backends (randomized bursty traces, hypothesis-style);
  * parked ring state (`StreamState`) survives an export/load round trip
    across a park-wake cycle and resumes bit-identically;
  * a zero-activity stream never consumes a pool slot (and departs with
    ``logits is None``);
  * skipped frames are priced as strictly positive uJ savings
    (`energy_summary` on the sim counters).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import api
from repro.api.program import CutieProgram
from repro.core.tcn import StreamState, TCNStream
from repro.serving import (
    ActivityGate,
    ContinuousBatcher,
    FleetRouter,
    SessionPool,
    StreamRequest,
    energy_summary,
    frame_energy_uj,
)

BACKENDS = ("ref", "fused")
GATE = ActivityGate(wake_threshold=8, park_threshold=3, park_after=2)


def tiny_graph(name="tiny_gating", tcn_steps=4):
    return api.CutieGraph(
        name=name, input_hw=(4, 4), input_ch=2, n_classes=3,
        tcn_steps=tcn_steps,
        layers=(api.conv2d(2, 4), api.global_pool(),
                api.tcn(4, 4, dilation=1), api.tcn(4, 4, dilation=2),
                api.last_step(), api.fc(4, 3)),
    )


def _deploy(graph, seed=0):
    prog = CutieProgram(graph)
    calib = (jax.random.uniform(jax.random.PRNGKey(seed + 1),
                                (2, 6, *graph.input_hw, graph.input_ch))
             < 0.3).astype(jnp.float32)
    return prog.quantize(prog.init(jax.random.PRNGKey(seed)), calib=calib)


_DEPLOYED = None


def get_deployed():
    """Module-cached tiny deployed program.  A plain function (not only a
    fixture) because ``@given`` tests can't take fixtures under the
    conftest hypothesis stub."""
    global _DEPLOYED
    if _DEPLOYED is None:
        _DEPLOYED = _deploy(tiny_graph())
    return _DEPLOYED


@pytest.fixture(scope="module")
def deployed():
    return get_deployed()


def bursty_clip(seed, frames=12, hw=(4, 4), ch=2, gate=GATE):
    """Alternating runs of quiet (< park_threshold events) and burst
    (>= wake_threshold events) frames — the trace shape the gate exists
    for."""
    r = np.random.default_rng(seed)
    clip = np.zeros((frames, *hw, ch), np.float32)
    burst = bool(r.integers(0, 2))
    t = 0
    while t < frames:
        for _ in range(int(r.integers(1, 5))):
            if t >= frames:
                break
            a = (int(r.integers(gate.wake_threshold, hw[0] * hw[1] * ch))
                 if burst else int(r.integers(0, gate.park_threshold)))
            flat = clip[t].reshape(-1)
            flat[r.choice(flat.size, size=a, replace=False)] = 1.0
            t += 1
        burst = not burst
    return clip


def processed_frames(clip, gate=GATE):
    """The oracle: frame indices the gate says get processed."""
    plan = gate.plan([ActivityGate.activity(f) for f in clip])
    return [t for t, p in enumerate(plan) if p]


def replay(deployed, clip, frame_idx, backend):
    """Lone batch-1 session fed exactly ``frame_idx``'s frames — what
    every gated pooled stream must reproduce bit-for-bit."""
    session = deployed.stream(batch=1, backend=backend)
    out = None
    for t in frame_idx:
        out = session.step(clip[t][None])
    return None if out is None else np.asarray(out)[0]


# ---------------------------------------------------------------------------
# ActivityGate.plan — the pure-policy semantics
# ---------------------------------------------------------------------------

class TestActivityGate:
    def test_streams_start_parked(self):
        # cold start: sub-wake activity never processes, even if "active"
        assert GATE.plan([GATE.park_threshold, GATE.wake_threshold - 1]) == \
            [False, False]

    def test_wake_frame_is_processed(self):
        assert GATE.plan([0, GATE.wake_threshold]) == [False, True]

    def test_hysteresis_rides_out_short_dips(self):
        # one quiet frame (< park_after) stays awake AND is processed
        w, q = GATE.wake_threshold, 0
        assert GATE.plan([w, q, w, q, w]) == [True] * 5

    def test_parks_after_consecutive_quiet(self):
        w = GATE.wake_threshold
        plan = GATE.plan([w, 0, 0, 0])
        assert plan == [True, True, False, False]  # 2nd quiet frame parks

    def test_awake_midband_keeps_processing(self):
        # activity in [park, wake) holds an awake stream awake, but
        # cannot wake a parked one — the flap guard
        mid = GATE.park_threshold
        w = GATE.wake_threshold
        assert GATE.plan([mid, w, mid, mid]) == [False, True, True, True]

    def test_zero_trace_all_skip(self):
        assert GATE.plan([0] * 6) == [False] * 6

    def test_activity_counts_nonzero_bins(self):
        f = np.zeros((4, 4, 2), np.float32)
        f[0, 0, 0] = 1.0
        f[1, 2, 1] = -1.0
        assert ActivityGate.activity(f) == 2

    def test_validation(self):
        with pytest.raises(ValueError):
            ActivityGate(wake_threshold=4, park_threshold=4)  # no hysteresis
        with pytest.raises(ValueError):
            ActivityGate(park_threshold=-1)
        with pytest.raises(ValueError):
            ActivityGate(park_after=0)


# ---------------------------------------------------------------------------
# The differential suite: gated pool == plan-selected lone session
# ---------------------------------------------------------------------------

class TestGatedBatcher:
    @given(seed=st.integers(0, 9999))
    @settings(max_examples=3, deadline=None)
    def test_gated_pool_matches_plan_replay(self, seed):
        """Randomized bursty traces through a contended 2-slot pool (5
        streams, staggered arrivals -> park/wake/evict/refill churn):
        every stream's processed-frame set must equal the oracle's and its
        logits must equal a lone session fed exactly those frames — on
        BOTH the ref and fused backends."""
        for backend in BACKENDS:
            self._check_differential(get_deployed(), backend, seed)

    def _check_differential(self, deployed, backend, seed):
        n_streams, T = 5, 12
        clips = {f"s{i}": bursty_clip(seed * 7 + i, frames=T)
                 for i in range(n_streams)}
        pool = SessionPool(deployed, 2, backend=backend)
        bat = ContinuousBatcher(pool, gate=GATE)
        for i, (sid, clip) in enumerate(clips.items()):
            bat.submit(StreamRequest(sid, jnp.asarray(clip), arrival=i % 3))
        results = {r.stream_id: r for r in bat.run()}
        assert len(results) == n_streams
        assert pool.trace_count == 1  # park/wake never retraces
        for sid, clip in clips.items():
            proc = processed_frames(clip)
            r = results[sid]
            assert r.frames_processed == len(proc), sid
            assert r.frames_skipped == T - len(proc), sid
            want = replay(deployed, clip, proc, backend)
            if want is None:
                assert r.logits is None, sid
            else:
                np.testing.assert_array_equal(r.logits, want, err_msg=sid)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_all_active_trace_equals_ungated(self, deployed, backend):
        """A trace of nothing but wake-strength frames processes every
        frame — gated results must be bit-identical to an ungated run."""
        r = np.random.default_rng(3)
        clips = {}
        for i in range(3):
            clip = np.zeros((6, 4, 4, 2), np.float32)
            for t in range(6):
                flat = clip[t].reshape(-1)
                flat[r.choice(flat.size, GATE.wake_threshold + 2,
                              replace=False)] = 1.0
            clips[f"s{i}"] = clip

        def run(gate):
            bat = ContinuousBatcher(
                SessionPool(deployed, 2, backend=backend), gate=gate)
            for i, (sid, clip) in enumerate(clips.items()):
                bat.submit(StreamRequest(sid, jnp.asarray(clip), arrival=i))
            return {r.stream_id: r for r in bat.run()}

        gated, ungated = run(GATE), run(None)
        for sid in clips:
            assert gated[sid].frames_processed == 6
            assert gated[sid].frames_skipped == 0
            np.testing.assert_array_equal(gated[sid].logits,
                                          ungated[sid].logits)

    def test_zero_activity_stream_never_takes_a_slot(self, deployed):
        """An all-quiet stream must finish without ever being admitted:
        no logits, no processed frames, admitted_tick == -1 — while a
        busy neighbour gets the slot."""
        quiet = np.zeros((6, 4, 4, 2), np.float32)
        busy = bursty_clip(11, frames=6)
        pool = SessionPool(deployed, 1, backend="ref")
        bat = ContinuousBatcher(pool, gate=GATE)
        bat.submit(StreamRequest("quiet", jnp.asarray(quiet), arrival=0))
        bat.submit(StreamRequest("busy", jnp.asarray(busy), arrival=0))
        results = {r.stream_id: r for r in bat.run()}
        r = results["quiet"]
        assert r.logits is None and r.pred is None
        assert r.frames_processed == 0 and r.frames_skipped == 6
        assert r.admitted_tick == -1  # never held a slot
        # the neighbour was unaffected
        proc = processed_frames(busy)
        np.testing.assert_array_equal(
            results["busy"].logits, replay(deployed, busy, proc, "ref"))

    def test_stream_state_roundtrips_across_park_wake(self, deployed):
        """The TinyVers retention seam: the ring parked out of the pool is
        a first-class `StreamState` — export/load round-trips it through a
        lone session mid-park, and the wake still resumes bit-exactly."""
        clip = np.zeros((8, 4, 4, 2), np.float32)
        for t in (0, 1, 2, 6, 7):  # burst, 3 quiet (parks at t=4), burst
            clip[t].reshape(-1)[: GATE.wake_threshold + 1] = 1.0
        assert processed_frames(clip) == [0, 1, 2, 3, 6, 7]
        pool = SessionPool(deployed, 1, backend="ref")
        bat = ContinuousBatcher(pool, gate=GATE)
        bat.submit(StreamRequest("s0", jnp.asarray(clip), arrival=0))
        # streams start cold in _parked; tick until the mid-clip park has
        # actually evicted the ring out of the pool
        while bat._gate_state["s0"].retained is None:
            bat.tick()
        gs = bat._gate_state["s0"]
        assert "s0" in bat._parked
        assert not gs.awake and gs.processed == 4  # frames 0..3 ran
        # the parked stream keeps the host logits of its last processed frame
        assert isinstance(gs.last_logits, np.ndarray)
        np.testing.assert_array_equal(
            gs.last_logits, replay(deployed, clip, [0, 1, 2, 3], "ref"))
        # the pool retains per-slot state (no batch dim); a batch-1 lone
        # session carries a leading batch axis — bridge it explicitly
        session = deployed.stream(batch=1, backend="ref")
        parked = gs.retained
        session.load_state(StreamState(
            ring=TCNStream(buf=parked.ring.buf[None],
                           cursor=parked.ring.cursor),
            steps_seen=parked.steps_seen))
        back = session.export_state()
        roundtripped = StreamState(
            ring=TCNStream(buf=back.ring.buf[0], cursor=back.ring.cursor),
            steps_seen=back.steps_seen)
        for a, b in zip(jax.tree_util.tree_leaves(parked),
                        jax.tree_util.tree_leaves(roundtripped)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        gs.retained = roundtripped  # resume from the round-tripped state
        (r,) = bat.run()
        assert r.frames_processed == 6 and bat.stats()["gating"]["wakes"] == 2
        assert isinstance(r.logits, np.ndarray)
        np.testing.assert_array_equal(
            r.logits, replay(deployed, clip, processed_frames(clip), "ref"))
        np.testing.assert_array_equal(gs.last_logits, r.logits)

    def test_cancel_parked_stream(self, deployed):
        clip = np.zeros((6, 4, 4, 2), np.float32)  # all quiet: parks forever
        bat = ContinuousBatcher(SessionPool(deployed, 1, backend="ref"),
                                gate=GATE)
        bat.submit(StreamRequest("s0", jnp.asarray(clip), arrival=0))
        bat.tick()
        assert bat.cancel("s0") == "parked"
        assert not bat.pending

    def test_gating_stats_block(self, deployed):
        clips = [bursty_clip(40 + i, frames=10) for i in range(3)]
        bat = ContinuousBatcher(SessionPool(deployed, 2, backend="ref"),
                                gate=GATE)
        for i, clip in enumerate(clips):
            bat.submit(StreamRequest(f"s{i}", jnp.asarray(clip), arrival=0))
        results = bat.run()
        st_ = bat.stats()
        g = st_["gating"]
        want_proc = sum(len(processed_frames(c)) for c in clips)
        assert g["frames_processed"] == want_proc == st_["frames_processed"]
        assert g["frames_skipped"] == 30 - want_proc
        assert g["frames_processed"] == sum(r.frames_processed
                                            for r in results)
        assert g["parked"] == 0  # everyone departed
        # ungated batchers don't grow the block
        bat2 = ContinuousBatcher(SessionPool(deployed, 1, backend="ref"))
        assert "gating" not in bat2.stats()


# ---------------------------------------------------------------------------
# Fleet integration + energy accounting
# ---------------------------------------------------------------------------

class TestGatedFleet:
    def test_router_threads_gate_into_buckets(self):
        dep_a = _deploy(tiny_graph("gate_fleet_a"), seed=4)
        dep_b = _deploy(tiny_graph("gate_fleet_b"), seed=5)
        router = FleetRouter(backend="ref", max_pool_size=2, gate=GATE)
        router.register("a", dep_a)
        router.register("b", dep_b, gate=ActivityGate(wake_threshold=9,
                                                      park_threshold=2))
        assert router.buckets["a"].batcher.gate is GATE
        assert router.buckets["b"].batcher.gate.wake_threshold == 9
        clips = {}
        for idx, name in enumerate(("a", "b")):
            for s in range(2):
                sid = f"{name}/{s}"
                clips[sid] = bursty_clip(60 + 10 * idx + s, frames=8)
                router.submit(StreamRequest(sid, jnp.asarray(clips[sid]),
                                            arrival=idx + s, net=name))
        results = {r.stream_id: r for r in router.run()}
        router.close()
        stats = router.stats()
        assert stats["gating"] is not None
        assert stats["gating"]["frames_processed"] == sum(
            r.frames_processed for r in results.values())
        for sid, r in results.items():
            name = sid.split("/")[0]
            gate = router.buckets[name].gate
            proc = processed_frames(clips[sid], gate)
            assert r.frames_processed == len(proc), sid
        # ungated fleets report no gating aggregate
        router2 = FleetRouter(backend="ref", max_pool_size=2)
        router2.register("a", dep_a)
        assert router2.stats()["gating"] is None

    def test_energy_summary_prices_skipped_frames(self, deployed):
        per = frame_energy_uj(deployed)
        assert per > 0
        s = energy_summary(deployed, frames_processed=40, frames_total=100,
                           completed=8)
        assert s["frames_skipped"] == 60
        assert s["duty_cycle"] == pytest.approx(0.4)
        assert s["energy_uj_per_frame"] == pytest.approx(per)
        assert s["energy_uj_saved"] == pytest.approx(60 * per)
        assert (s["energy_uj_per_classification"]
                < s["energy_uj_per_classification_ungated"])

    def test_energy_summary_no_classifications(self, deployed):
        s = energy_summary(deployed, frames_processed=0, frames_total=10,
                           completed=0)
        assert s["energy_uj_saved"] > 0
        assert np.isnan(s["energy_uj_per_classification"])
