"""`repro.serving`: continuous-batching pool over StreamSession state.

The serving contract under test:
  * slot p of a P-wide pool is bit-exact vs an independent batch-1
    `StreamSession` fed the same frames, on the fused AND ref backends,
    through admissions, evictions, refills, partial ticks, and resets;
  * admit/evict/refill never retrace the jitted step (trace_count == 1);
  * `StreamState` is a first-class value: evicted state resumes in a
    standalone session (and vice versa) with identical logits.
"""
import subprocess
import sys
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.api.program import CutieProgram
from repro.core.tcn import TCNStream
from repro.serving import (
    ContinuousBatcher,
    PoolFullError,
    PoolState,
    SessionPool,
    StreamRequest,
    clear_lanes,
    gather_slot,
    masked_push,
    ordered_windows,
    scatter_slot,
)
from repro.serving.masking import FRESH, STEP, split_lanes

BACKENDS = ("ref", "fused")


def tiny_graph(tcn_steps: int = 4) -> api.CutieGraph:
    return api.CutieGraph(
        name="tiny_serving", input_hw=(4, 4), input_ch=2, n_classes=3,
        tcn_steps=tcn_steps,
        layers=(api.conv2d(2, 4), api.global_pool(),
                api.tcn(4, 4, dilation=1), api.tcn(4, 4, dilation=2),
                api.last_step(), api.fc(4, 3)),
    )


def clips_for(graph, n_streams: int, frames: int, seed: int = 0):
    shape = (n_streams, frames, *graph.input_hw, graph.input_ch)
    return (jax.random.uniform(jax.random.PRNGKey(seed), shape) < 0.3
            ).astype(jnp.float32)


@pytest.fixture(scope="module")
def deployed():
    prog = CutieProgram(tiny_graph())
    frames = clips_for(prog.graph, 2, 6, seed=1)
    return prog.quantize(prog.init(jax.random.PRNGKey(0)), calib=frames)


def exact(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# masking: the pure state algebra
# ---------------------------------------------------------------------------

class TestMasking:
    def test_masked_push_freezes_inactive_slots(self):
        state = PoolState.create(3, 4, 2)
        feats = jnp.arange(6, dtype=jnp.float32).reshape(3, 2)
        active = jnp.array([True, False, True])
        new = masked_push(state, feats, active)
        assert np.asarray(new.buf[0, 0] == feats[0]).all()
        assert not np.asarray(new.buf[1]).any()          # frozen slot: zeros
        assert list(np.asarray(new.cursor)) == [1, 0, 1]
        assert list(np.asarray(new.steps)) == [1, 0, 1]

    def test_ordered_windows_matches_per_stream_ring(self):
        """Per-slot roll == each slot's own TCNStream.ordered()."""
        state = PoolState.create(2, 3, 2)
        rings = [TCNStream.create(3, 2) for _ in range(2)]
        pushes = [3, 5]  # different ages -> different cursors
        for slot, n in enumerate(pushes):
            for t in range(n):
                v = jnp.full((2,), 10 * slot + t, jnp.float32)
                rings[slot] = rings[slot].push(v)
                active = jnp.arange(2) == slot
                state = masked_push(
                    state, jnp.stack([v, v]), active.astype(bool)
                )
        windows = ordered_windows(state)
        for slot in range(2):
            exact(windows[slot], rings[slot].ordered())

    def test_scatter_gather_round_trip(self):
        state = PoolState.create(3, 4, 2)
        feats = jnp.ones((3, 2))
        for _ in range(5):
            state = masked_push(state, feats, jnp.array([True, True, False]))
        st1 = gather_slot(state, 1)
        assert int(st1.steps_seen) == 5
        state2 = scatter_slot(PoolState.create(3, 4, 2), 1, st1)
        exact(gather_slot(state2, 1).ring.buf, st1.ring.buf)
        assert int(gather_slot(state2, 1).ring.cursor) == int(st1.ring.cursor)

    def test_scatter_rejects_batched_and_misshaped_states(self):
        from repro.core.tcn import StreamState
        state = PoolState.create(2, 4, 2)
        with pytest.raises(ValueError, match="batch-free"):
            scatter_slot(state, 0, StreamState.create(4, 2, batch=3))
        with pytest.raises(ValueError, match="does not fit"):
            scatter_slot(state, 0, StreamState.create(5, 2))

    def test_clear_slot_is_per_slot(self):
        state = PoolState.create(2, 4, 2)
        state = masked_push(state, jnp.ones((2, 2)), jnp.array([True, True]))
        state = clear_lanes(state, jnp.array([True, False]))
        assert not np.asarray(state.buf[0]).any()
        assert np.asarray(state.buf[1, 0]).all()
        assert list(np.asarray(state.steps)) == [0, 1]
        assert list(np.asarray(state.cursor)) == [0, 1]

    def test_split_lanes_reads_bool_masks_and_codes(self):
        """A bool mask is the lane code with the FRESH bit unset."""
        step, fresh = split_lanes(jnp.array([True, False, True]))
        assert list(np.asarray(step)) == [True, False, True]
        assert not np.asarray(fresh).any()
        code = jnp.array([0, STEP, FRESH, STEP | FRESH], jnp.int8)
        step, fresh = split_lanes(code)
        assert list(np.asarray(step)) == [False, True, False, True]
        assert list(np.asarray(fresh)) == [False, False, True, True]


# ---------------------------------------------------------------------------
# the pool: bit-exactness + continuous batching
# ---------------------------------------------------------------------------

class TestSessionPool:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_bit_exact_vs_independent_sessions_with_churn(self, deployed, backend):
        """The acceptance criterion: admissions, a mid-flight evict+refill,
        and a partial tick — every pooled logit equals its lone session."""
        frames = clips_for(deployed.graph, 4, 6, seed=2)
        pool = SessionPool(deployed, 3, backend=backend)
        sessions = [deployed.stream(batch=1, backend=backend) for _ in range(4)]

        def check(out, i, t):
            want = sessions[i].step(frames[i:i + 1, t])
            exact(out, np.asarray(want)[0])

        pool.admit("s0"); pool.admit("s1"); pool.admit("s2")
        for t in range(3):
            out = pool.step({"s0": frames[0, t], "s1": frames[1, t],
                             "s2": frames[2, t]})
            check(out["s0"], 0, t); check(out["s1"], 1, t); check(out["s2"], 2, t)
        pool.evict("s1")                     # departs mid-flight
        pool.admit("s3")                     # slot refilled, no retrace
        for t in range(3, 6):
            out = pool.step({"s0": frames[0, t], "s3": frames[3, t - 3],
                             "s2": frames[2, t]})
            check(out["s0"], 0, t); check(out["s3"], 3, t - 3)
            check(out["s2"], 2, t)
        out = pool.step({"s3": frames[3, 3]})  # partial tick: others frozen
        check(out["s3"], 3, 3)
        assert pool.trace_count == 1

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_registry_smoke_net_exact(self, backend):
        """Same contract on the real (shrunken) DVS registry net."""
        prog = api.get_net("dvs_cnn_tcn_smoke")
        frames = (jax.random.uniform(jax.random.PRNGKey(3), (2, 3, 32, 32, 2))
                  < 0.05).astype(jnp.float32)
        dep = prog.quantize(prog.init(jax.random.PRNGKey(0)), calib=frames)
        pool = dep.serve(2, backend=backend)
        pool.admit("a"); pool.admit("b")
        s = [dep.stream(batch=1, backend=backend) for _ in range(2)]
        for t in range(3):
            out = pool.step({"a": frames[0, t], "b": frames[1, t]})
            exact(out["a"], np.asarray(s[0].step(frames[0:1, t]))[0])
            exact(out["b"], np.asarray(s[1].step(frames[1:2, t]))[0])

    def test_evicted_then_refilled_slot_matches_fresh_session(self, deployed):
        """A slot that hosted a long-running stream, evicted and refilled,
        serves the newcomer exactly like a fresh session — no state leaks
        across tenants."""
        frames = clips_for(deployed.graph, 2, 5, seed=4)
        pool = SessionPool(deployed, 1, backend="ref")
        pool.admit("old")
        for t in range(5):
            pool.step({"old": frames[0, t]})
        pool.evict("old")
        pool.admit("new")                    # same physical slot
        fresh = deployed.stream(batch=1, backend="ref")
        assert pool.steps_seen("new") == 0 and not pool.window_warm("new")
        for t in range(5):
            out = pool.step({"new": frames[1, t]})
            exact(out["new"], np.asarray(fresh.step(frames[1:2, t]))[0])

    def test_state_migrates_pool_to_session_and_back(self, deployed):
        """evict -> StreamSession.load_state -> export -> admit(state=...)
        round-trips with bit-identical logits vs an uninterrupted session."""
        frames = clips_for(deployed.graph, 1, 8, seed=5)[0]
        oracle = deployed.stream(batch=None, backend="ref")
        pool_a = SessionPool(deployed, 2, backend="ref")
        pool_a.admit("m")
        outs = [pool_a.step({"m": frames[t]})["m"] for t in range(3)]
        state = pool_a.evict("m")
        session = deployed.stream(batch=None, backend="ref")
        session.load_state(state)
        assert session.steps_seen == 3
        outs += [session.step(frames[t][None])[0] for t in range(3, 5)]
        pool_b = SessionPool(deployed, 3, backend="ref")
        pool_b.admit("m", state=session.export_state())
        assert pool_b.steps_seen("m") == 5
        outs += [pool_b.step({"m": frames[t]})["m"] for t in range(5, 8)]
        for t in range(8):
            exact(outs[t], oracle.step(frames[t][None])[0])

    def test_per_slot_reset(self, deployed):
        """reset(sid) zeroes one lane mid-flight; the neighbour's stream is
        untouched and the reset stream equals a fresh session."""
        frames = clips_for(deployed.graph, 2, 6, seed=6)
        pool = SessionPool(deployed, 2, backend="ref")
        s0 = deployed.stream(batch=1, backend="ref")
        s1 = deployed.stream(batch=1, backend="ref")
        pool.admit("a"); pool.admit("b")
        for t in range(3):
            pool.step({"a": frames[0, t], "b": frames[1, t]})
            s0.step(frames[0:1, t])
        pool.reset("b")
        s1b = deployed.stream(batch=1, backend="ref")  # fresh oracle for b
        assert pool.steps_seen("b") == 0
        for t in range(3, 6):
            out = pool.step({"a": frames[0, t], "b": frames[1, t]})
            exact(out["a"], np.asarray(s0.step(frames[0:1, t]))[0])
            exact(out["b"], np.asarray(s1b.step(frames[1:2, t]))[0])
        del s1

    def test_admission_bookkeeping_and_errors(self, deployed):
        pool = SessionPool(deployed, 2, backend="ref")
        pool.admit("x")
        with pytest.raises(ValueError, match="already admitted"):
            pool.admit("x")
        pool.admit("y")
        assert pool.occupancy == 1.0 and pool.free_slots == 0
        with pytest.raises(PoolFullError):
            pool.admit("z")
        with pytest.raises(KeyError):
            pool.evict("ghost")
        with pytest.raises(KeyError):
            pool.step({"ghost": np.zeros((4, 4, 2), np.float32)})
        with pytest.raises(ValueError, match="frame shape"):
            pool.step({"x": np.zeros((5, 5, 2), np.float32)})
        pool.evict("x")
        assert pool.occupancy == 0.5 and "x" not in pool and "y" in pool

    def test_window_warm_per_slot(self, deployed):
        T = deployed.graph.tcn_steps
        frames = clips_for(deployed.graph, 2, T + 1, seed=7)
        pool = SessionPool(deployed, 2, backend="ref")
        pool.admit("a")
        for t in range(T):
            pool.step({"a": frames[0, t]})
        pool.admit("b")                       # admitted late: cold window
        pool.step({"a": frames[0, T], "b": frames[1, 0]})
        assert pool.window_warm("a") and not pool.window_warm("b")
        assert pool.steps_seen("a") == T + 1 and pool.steps_seen("b") == 1

    def test_spatial_net_rejected(self):
        prog = api.get_net("cifar10_tnn_smoke")
        dep = prog.quantize(prog.init(jax.random.PRNGKey(0)))
        with pytest.raises(ValueError, match="no TCN memory"):
            dep.serve(2)


# ---------------------------------------------------------------------------
# slot surgery as data: fresh lanes zeroed by the step, release without read
# ---------------------------------------------------------------------------

def zero_state_of(pool):
    buf = np.asarray(pool.state.buf)
    return np.zeros(buf.shape[1:], buf.dtype)


class TestFreshLanes:
    """A cold admit or a reset marks the slot fresh on the host and the
    next step zeroes it; `release` frees a slot without reading it.  Every
    pooled logit still equals a lone session, and reads made while a slot
    is pending answer as the zeroed slot would."""

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_release_then_refill_same_slot_under_churn(self, deployed, backend):
        """Streams of different lengths share 2 slots; each departure is a
        `release` and its slot is refilled before the next step, so every
        refill lands on a slot whose previous tenant was never read."""
        lengths = [3, 1, 4, 2, 5, 1, 3]
        frames = clips_for(deployed.graph, len(lengths), max(lengths), seed=40)
        pool = SessionPool(deployed, 2, backend=backend)
        queue = list(range(len(lengths)))
        fed, oracles = {}, {}
        while queue or fed:
            while queue and pool.free_slots:
                i = queue.pop(0)
                pool.admit(f"s{i}")
                assert pool.steps_seen(f"s{i}") == 0
                fed[i], oracles[i] = 0, deployed.stream(batch=1, backend=backend)
            out = pool.step({f"s{i}": frames[i, t] for i, t in fed.items()})
            for i, t in list(fed.items()):
                want = oracles[i].step(frames[i:i + 1, t])
                exact(out[f"s{i}"], np.asarray(want)[0])
                fed[i] += 1
                assert pool.steps_seen(f"s{i}") == fed[i]
                if fed[i] == lengths[i]:
                    pool.release(f"s{i}")
                    del fed[i]
        assert len(pool) == 0 and pool.trace_count == 1

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_admit_then_evict_before_any_step_is_zero_state(
        self, deployed, backend
    ):
        """A slot whose previous tenant stepped 5 frames: a newcomer admitted
        there reads 0 / not warm, and evicted before any step hands back a
        zero `StreamState`, never the old tenant's ring."""
        T = deployed.graph.tcn_steps
        frames = clips_for(deployed.graph, 2, T + 2, seed=41)
        pool = SessionPool(deployed, 1, backend=backend)
        pool.admit("old")
        for t in range(T + 1):
            pool.step({"old": frames[0, t]})
        assert pool.window_warm("old")
        pool.release("old")
        pool.admit("new")
        assert pool.steps_seen("new") == 0 and not pool.window_warm("new")
        st = pool.evict("new")
        exact(st.ring.buf, zero_state_of(pool))
        assert int(st.ring.cursor) == 0 and int(st.steps_seen) == 0
        assert st.ring.buf.dtype == pool.state.buf.dtype
        # the zero state resumes like a fresh session
        pool.admit("new", state=st)
        fresh = deployed.stream(batch=1, backend=backend)
        for t in range(T + 1):
            out = pool.step({"new": frames[1, t]})
            exact(out["new"], np.asarray(fresh.step(frames[1:2, t]))[0])
            assert pool.window_warm("new") == (t + 1 >= T)
        assert pool.trace_count == 1

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_released_slot_refilled_with_state_keeps_migrated_ring(
        self, deployed, backend
    ):
        """A slot left marked fresh by a cold admit that never stepped, then
        refilled with `admit(state=...)`: the step must not zero the
        migrated ring."""
        frames = clips_for(deployed.graph, 1, 7, seed=42)[0]
        oracle = deployed.stream(batch=1, backend=backend)
        src = SessionPool(deployed, 2, backend=backend)
        src.admit("m")
        outs = [src.step({"m": frames[t]})["m"] for t in range(3)]
        state = src.evict("m")
        dst = SessionPool(deployed, 1, backend=backend)
        dst.admit("ghost")                  # marks the only slot fresh
        dst.release("ghost")                # leaves before any step
        dst.admit("m", state=state)
        assert dst.steps_seen("m") == 3
        outs += [dst.step({"m": frames[t]})["m"] for t in range(3, 7)]
        for t in range(7):
            exact(outs[t], np.asarray(oracle.step(frames[None, t]))[0])
        assert src.trace_count == 1 and dst.trace_count == 1

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_reset_through_the_fresh_lane(self, deployed, backend):
        """reset() zeroes one lane through the next step, even a step in
        which the reset stream does not push (FRESH without STEP); the
        neighbour is untouched."""
        frames = clips_for(deployed.graph, 2, 7, seed=43)
        pool = SessionPool(deployed, 2, backend=backend)
        keep = deployed.stream(batch=1, backend=backend)
        pool.admit("a"); pool.admit("b")
        for t in range(3):
            pool.step({"a": frames[0, t], "b": frames[1, t]})
            keep.step(frames[0:1, t])
        pool.reset("b")
        assert pool.steps_seen("b") == 0 and pool.steps_seen("a") == 3
        out = pool.step({"a": frames[0, 3]})  # b is zeroed, not stepped
        exact(out["a"], np.asarray(keep.step(frames[0:1, 3]))[0])
        on_device = gather_slot(pool.state, pool.slot_of("b"))
        exact(on_device.ring.buf, zero_state_of(pool))
        assert int(on_device.ring.cursor) == 0 == int(on_device.steps_seen)
        assert pool.steps_seen("a") == 4
        fresh = deployed.stream(batch=1, backend=backend)
        for t in range(4, 7):
            out = pool.step({"a": frames[0, t], "b": frames[1, t]})
            exact(out["a"], np.asarray(keep.step(frames[0:1, t]))[0])
            exact(out["b"], np.asarray(fresh.step(frames[1:2, t]))[0])
        assert pool.trace_count == 1

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_step_prepared_with_plain_bool_active_equals_step(
        self, deployed, backend
    ):
        """A caller's bool `active` still drives the step: the pool ORs its
        pending fresh marks into it, so a slot refilled after a departure
        is zeroed exactly as through `step`."""
        frames = clips_for(deployed.graph, 3, 4, seed=44)
        a = SessionPool(deployed, 2, backend=backend)
        b = SessionPool(deployed, 2, backend=backend)
        for p in (a, b):
            p.admit("x"); p.admit("y")
        for t in range(4):
            if t == 2:
                for p in (a, b):
                    p.release("y"); p.admit("z")
            fr = {sid: frames[i, t] for i, sid in enumerate(("x", "y", "z"))
                  if sid in a}
            active = np.zeros((2,), bool)
            batch = np.zeros((2, *a.frame_shape), np.float32)
            for sid, f in fr.items():
                active[a.slot_of(sid)] = True
                batch[a.slot_of(sid)] = np.asarray(f)
            logits = a.step_prepared(batch, active)
            assert active.dtype == bool          # the caller's mask untouched
            want = b.step(fr)
            for sid in fr:
                exact(logits[a.slot_of(sid)], want[sid])
        assert a.trace_count == 1 and b.trace_count == 1

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_retire_and_cancel_never_gather(self, deployed, backend, monkeypatch):
        """Departures and in-flight cancellations discard the state, so the
        batcher never reads a slot back: `gather_slot` may not run."""
        import repro.serving.pool as pool_mod

        def refuse(*_):
            raise AssertionError("gather_slot called for a discarded state")

        monkeypatch.setattr(pool_mod, "gather_slot", refuse)
        lengths = [2, 4, 1, 3, 3]
        frames = clips_for(deployed.graph, len(lengths), 4, seed=45)
        pool = SessionPool(deployed, 2, backend=backend)
        batcher = ContinuousBatcher(pool)
        for i, n in enumerate(lengths):
            batcher.submit(StreamRequest(f"s{i}", frames[i, :n]))
        batcher.tick()
        assert batcher.cancel("s1") == "inflight"
        results = batcher.run()
        assert {r.stream_id for r in results} == {"s0", "s2", "s3", "s4"}
        for r in results:
            i = int(r.stream_id[1:])
            oracle = deployed.stream(batch=1, backend=backend)
            for t in range(lengths[i]):
                want = oracle.step(frames[i:i + 1, t])
            exact(r.logits, np.asarray(want)[0])
        assert pool.trace_count == 1

    def test_spans_count_fresh_lanes_and_gathers(self, deployed):
        """`pool.step` carries how many lanes it zeroed; `pool.evict` says
        whether the slot's state was read (evict) or not (release)."""
        from repro.obs import Tracer

        frames = clips_for(deployed.graph, 3, 2, seed=46)
        tracer = Tracer()
        pool = SessionPool(deployed, 3, backend="ref", tracer=tracer)
        pool.admit("a"); pool.admit("b")
        pool.step({"a": frames[0, 0]})        # b zeroed too, not stepped
        pool.admit("c")
        pool.step({"a": frames[0, 1], "c": frames[2, 0]})
        pool.step({"c": frames[2, 1]})
        pool.evict("a"); pool.release("b")
        spans = [e for e in tracer.events() if e.phase == "X"]
        assert [e.args["fresh"] for e in spans if e.name == "pool.step"] == [2, 1, 0]
        assert [e.args["gathered"] for e in spans if e.name == "pool.evict"] == [1, 0]
        assert pool.trace_count == 1


# ---------------------------------------------------------------------------
# the scheduler: arrivals / departures / refill policy
# ---------------------------------------------------------------------------

class TestContinuousBatcher:
    def test_staggered_arrivals_all_served_and_exact(self, deployed):
        """6 streams x 4 frames through 2 slots, arrivals at tick i: every
        stream completes and its final logits equal a lone session replay."""
        frames = clips_for(deployed.graph, 6, 4, seed=8)
        pool = SessionPool(deployed, 2, backend="ref")
        batcher = ContinuousBatcher(pool)
        for i in range(6):
            batcher.submit(StreamRequest(f"s{i}", frames[i], label=i % 3,
                                         arrival=i))
        results = batcher.run()
        assert len(results) == 6
        assert pool.trace_count == 1
        stats = batcher.stats()
        assert stats["completed"] == 6
        assert stats["frames_processed"] == 24
        assert 0.0 < stats["mean_occupancy"] <= 1.0
        for r in results:
            session = deployed.stream(batch=1, backend="ref")
            idx = int(r.stream_id[1:])
            for t in range(4):
                want = session.step(frames[idx:idx + 1, t])
            exact(r.logits, np.asarray(want)[0])
            assert r.n_frames == 4 and r.finished_tick >= r.admitted_tick

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_tick_returns_rows_of_one_host_array(self, deployed, backend):
        """Each non-empty tick hands back host rows of ONE `[P, n_classes]`
        copy of the step's logits, through departures and refills, and each
        row is bit-exact vs a lone session fed the same frames."""
        P, n_classes = 3, deployed.graph.n_classes
        lengths = [2, 3, 4, 1, 3, 2, 4]
        frames = clips_for(deployed.graph, len(lengths), max(lengths), seed=31)
        batcher = ContinuousBatcher(SessionPool(deployed, P, backend=backend))
        oracles = {}
        for i, n in enumerate(lengths):
            batcher.submit(StreamRequest(f"s{i}", frames[i, :n]))
            oracles[f"s{i}"] = deployed.stream(batch=1, backend=backend)
        fed = {sid: 0 for sid in oracles}
        ticks = 0
        while batcher.pending:
            out = batcher.tick()
            ticks += 1
            assert out
            (base,) = {id(y.base): y.base for y in out.values()}.values()
            assert isinstance(base, np.ndarray)
            assert base.shape == (P, n_classes) and base.dtype == np.float32
            for sid, y in out.items():
                assert isinstance(y, np.ndarray) and y.shape == (n_classes,)
                assert np.shares_memory(y, base)
                i, t = int(sid[1:]), fed[sid]
                want = oracles[sid].step(frames[i:i + 1, t])
                exact(y, np.asarray(want)[0])
                fed[sid] += 1
        assert fed == {f"s{i}": n for i, n in enumerate(lengths)}
        assert ticks < sum(lengths)       # slots were shared, then refilled
        for r in batcher.results:
            assert isinstance(r.logits, np.ndarray)

    def test_future_head_does_not_block_admissible_streams(self, deployed):
        """A far-future request at the head of the queue must not starve a
        later-submitted stream whose arrival has already passed."""
        frames = clips_for(deployed.graph, 2, 2, seed=13)
        batcher = ContinuousBatcher(SessionPool(deployed, 1, backend="ref"))
        batcher.submit(StreamRequest("future", frames[0], arrival=6))
        batcher.submit(StreamRequest("now", frames[1], arrival=0))
        results = batcher.run(max_ticks=30)
        by_id = {r.stream_id: r for r in results}
        assert set(by_id) == {"future", "now"}
        assert by_id["now"].admitted_tick == 0       # served immediately
        assert by_id["future"].admitted_tick == 6

    def test_arrival_gap_advances_time(self, deployed):
        """A lone request arriving at tick 3 still gets served (idle ticks
        advance logical time instead of deadlocking)."""
        frames = clips_for(deployed.graph, 1, 2, seed=9)
        batcher = ContinuousBatcher(SessionPool(deployed, 2, backend="ref"))
        batcher.submit(StreamRequest("late", frames[0], arrival=3))
        results = batcher.run(max_ticks=20)
        assert len(results) == 1 and results[0].admitted_tick == 3

    def test_submit_validation(self, deployed):
        frames = clips_for(deployed.graph, 1, 2, seed=10)
        batcher = ContinuousBatcher(SessionPool(deployed, 2, backend="ref"))
        batcher.submit(StreamRequest("dup", frames[0]))
        with pytest.raises(ValueError, match="duplicate"):
            batcher.submit(StreamRequest("dup", frames[0]))
        with pytest.raises(ValueError, match="frames must be"):
            StreamRequest("bad", frames[0, 0])
        with pytest.raises(ValueError, match="empty clip"):
            StreamRequest("empty", frames[0][:0])

    def test_results_report_accuracy(self, deployed):
        frames = clips_for(deployed.graph, 2, 3, seed=11)
        pool = SessionPool(deployed, 2, backend="ref")
        batcher = ContinuousBatcher(pool)
        batcher.submit(StreamRequest("u", frames[0], label=0))
        batcher.submit(StreamRequest("v", frames[1]))  # unlabeled
        results = batcher.run()
        labeled = [r for r in results if r.label is not None]
        assert len(labeled) == 1 and labeled[0].correct in (True, False)
        assert [r for r in results if r.label is None][0].correct is None
        acc = batcher.stats()["accuracy"]
        assert acc in (0.0, 1.0)  # only the labeled stream counts


class TestSchedulerEdgeCases:
    """The corners the fleet layer leans on: cancellation of pending and
    in-flight streams, refill ordering under overflow, pool swaps
    mid-flight, and the prepare/step_prepared split."""

    def test_cancel_queued_request_never_touches_pool(self, deployed):
        frames = clips_for(deployed.graph, 3, 3, seed=20)
        pool = SessionPool(deployed, 1, backend="ref")
        batcher = ContinuousBatcher(pool)
        batcher.submit(StreamRequest("a", frames[0]))
        batcher.submit(StreamRequest("b", frames[1]))   # waits in queue
        batcher.tick()
        assert batcher.cancel("b") == "queued"
        results = batcher.run()
        assert {r.stream_id for r in results} == {"a"}
        stats = batcher.stats()
        assert stats["cancelled"] == 1 and batcher.cancelled == ["b"]
        assert pool.trace_count == 1

    def test_cancel_inflight_frees_slot_and_keeps_neighbors_exact(
        self, deployed
    ):
        """Mid-clip departure: the cancelled stream vanishes without a
        StreamResult, its slot refills next tick, and the surviving
        stream's logits stay bit-exact through the churn."""
        frames = clips_for(deployed.graph, 3, 5, seed=21)
        pool = SessionPool(deployed, 2, backend="ref")
        batcher = ContinuousBatcher(pool)
        batcher.submit(StreamRequest("keep", frames[0]))
        batcher.submit(StreamRequest("drop", frames[1]))
        batcher.submit(StreamRequest("next", frames[2]))  # queued (pool full)
        batcher.tick(); batcher.tick()
        assert batcher.cancel("drop") == "inflight"
        results = batcher.run()
        assert {r.stream_id for r in results} == {"keep", "next"}
        oracle = deployed.stream(batch=1, backend="ref")
        for t in range(5):
            want = oracle.step(frames[0:1, t])
        by_id = {r.stream_id: r for r in results}
        exact(by_id["keep"].logits, np.asarray(want)[0])
        assert pool.trace_count == 1
        with pytest.raises(KeyError):
            batcher.cancel("drop")                      # already gone
        with pytest.raises(KeyError):
            batcher.cancel("keep")                      # already finished

    def test_refill_ordering_under_overflow_is_fifo(self, deployed):
        """8 streams through 2 slots: slots refill in submission order
        among admissible requests — the earliest-submitted queued stream
        always takes the freed slot."""
        frames = clips_for(deployed.graph, 8, 2, seed=22)
        batcher = ContinuousBatcher(SessionPool(deployed, 2, backend="ref"))
        for i in range(8):
            batcher.submit(StreamRequest(f"s{i}", frames[i]))  # all arrival=0
        results = batcher.run()
        admitted = {r.stream_id: r.admitted_tick for r in results}
        order = sorted(admitted, key=lambda sid: (admitted[sid], int(sid[1:])))
        assert order == [f"s{i}" for i in range(8)]
        # pairwise: s0,s1 first, then s2,s3 on the freed slots, ...
        for i in range(8):
            assert admitted[f"s{i}"] == (i // 2) * 2

    def test_swap_pool_midflight_is_bit_exact(self, deployed):
        """The autoscaler's mechanism: migrating in-flight streams to a
        wider pool (and back down) preserves every subsequent logit."""
        frames = clips_for(deployed.graph, 2, 6, seed=23)
        small = SessionPool(deployed, 2, backend="ref")
        wide = SessionPool(deployed, 4, backend="ref")
        batcher = ContinuousBatcher(small)
        oracles = [deployed.stream(batch=1, backend="ref") for _ in range(2)]
        batcher.submit(StreamRequest("a", frames[0]))
        batcher.submit(StreamRequest("b", frames[1]))
        out = [batcher.tick(), batcher.tick()]
        assert batcher.swap_pool(wide) is small         # old pool handed back
        assert batcher.swap_pool(wide) is wide          # no-op on same pool
        out += [batcher.tick() for _ in range(4)]
        for t in range(6):
            exact(out[t]["a"], np.asarray(oracles[0].step(frames[0:1, t]))[0])
            exact(out[t]["b"], np.asarray(oracles[1].step(frames[1:2, t]))[0])
        assert small.trace_count == 1 and wide.trace_count == 1
        assert small.occupancy == 0.0                   # fully migrated out

    def test_swap_pool_rejects_too_small_target(self, deployed):
        frames = clips_for(deployed.graph, 2, 4, seed=24)
        batcher = ContinuousBatcher(SessionPool(deployed, 2, backend="ref"))
        batcher.submit(StreamRequest("a", frames[0]))
        batcher.submit(StreamRequest("b", frames[1]))
        batcher.tick()
        tiny = SessionPool(deployed, 1, backend="ref")
        with pytest.raises(ValueError, match="cannot swap"):
            batcher.swap_pool(tiny)

    def test_stats_expose_queue_depth_and_per_net(self, deployed):
        frames = clips_for(deployed.graph, 4, 3, seed=25)
        batcher = ContinuousBatcher(SessionPool(deployed, 1, backend="ref"))
        batcher.submit(StreamRequest("a", frames[0], net="net_a"))
        batcher.submit(StreamRequest("b", frames[1], net="net_b"))
        batcher.submit(StreamRequest("c", frames[2], net="net_a"))
        batcher.submit(StreamRequest("d", frames[3]))   # no net: pool's name
        batcher.tick()
        stats = batcher.stats()
        assert stats["queue_depth"] == 3 and stats["inflight"] == 1
        assert batcher.admissible() == 3
        assert stats["per_net"]["net_a"] == {
            "completed": 0, "inflight": 1, "queued": 1}
        assert stats["per_net"]["net_b"]["queued"] == 1
        batcher.run()
        stats = batcher.stats()
        assert stats["queue_depth"] == 0 and stats["inflight"] == 0
        assert stats["per_net"]["net_a"]["completed"] == 2
        # the un-tagged stream falls back to the serving program's name
        assert stats["per_net"]["tiny_serving"]["completed"] == 1
        assert stats["latency_ms_p50"] > 0.0
        assert stats["latency_ms_p99"] >= stats["latency_ms_p50"]

    def test_prepare_step_prepared_equals_step(self, deployed):
        """The split the feeder pipelines through is just step() unbundled:
        same logits, and caller-owned buffers are reused in place."""
        frames = clips_for(deployed.graph, 2, 3, seed=26)
        a = SessionPool(deployed, 2, backend="ref")
        b = SessionPool(deployed, 2, backend="ref")
        for p in (a, b):
            p.admit("x"); p.admit("y")
        buf = np.full((2, *a.frame_shape), 7.0, np.float32)
        act = np.ones((2,), bool)
        for t in range(3):
            fr = {"x": frames[0, t], "y": frames[1, t]}
            batch, active = a.prepare(fr, out_batch=buf, out_active=act)
            assert batch is buf and active is act       # in-place reuse
            logits = a.step_prepared(batch, active)
            got = {sid: logits[a.slot_of(sid)] for sid in fr}
            want = b.step(fr)
            exact(got["x"], want["x"]); exact(got["y"], want["y"])
        assert a.trace_count == 1


# ---------------------------------------------------------------------------
# batch-axis sharding (forced multi-device CPU, subprocess)
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
assert len(jax.local_devices()) == 4, jax.local_devices()
from repro.api.program import CutieProgram
from repro.serving import SessionPool
from tests.test_serving import tiny_graph, clips_for

prog = CutieProgram(tiny_graph())
frames = clips_for(prog.graph, 4, 3, seed=12)
dep = prog.quantize(prog.init(jax.random.PRNGKey(0)), calib=frames)
sharded = SessionPool(dep, 4, backend="ref", sharding="auto")
plain = SessionPool(dep, 4, backend="ref")
assert sharded.sharding is not None
for i in range(4):
    sharded.admit(f"s{i}"); plain.admit(f"s{i}")
for t in range(3):
    fr = {f"s{i}": frames[i, t] for i in range(4)}
    a, b = sharded.step(fr), plain.step(fr)
    for k in fr:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
from repro.serving import ContinuousBatcher, StreamRequest
outs = []
for pool in (SessionPool(dep, 4, backend="ref", sharding="auto"),
             SessionPool(dep, 4, backend="ref")):
    bat = ContinuousBatcher(pool)
    for i in range(4):
        bat.submit(StreamRequest(f"s{i}", frames[i, :1 + i % 3]))
    outs.append([bat.tick() for _ in range(3)])
for got, want in zip(*outs):
    assert got.keys() == want.keys()
    for k in got:
        assert isinstance(got[k], np.ndarray)
        np.testing.assert_array_equal(got[k], want[k])
print("SHARDED-OK")
"""


def test_pool_sharding_bit_exact_on_forced_devices():
    """The pool axis laid across 4 forced CPU devices returns the same bits
    as the single-device pool (subprocess: XLA device count is init-time)."""
    repo = Path(__file__).resolve().parents[1]
    env = {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": f"{repo / 'src'}:{repo}",
        "PATH": "/usr/bin:/bin:/usr/local/bin",
    }
    proc = subprocess.run(
        [sys.executable, "-c", _SHARD_SCRIPT], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr
    assert "SHARDED-OK" in proc.stdout


_SHARD_RULES_SCRIPT = """
import jax, numpy as np
assert len(jax.local_devices()) == 4, jax.local_devices()
from repro.api.program import CutieProgram
from repro.serving import FleetRouter, SessionPool
from tests.test_serving import tiny_graph, clips_for

prog = CutieProgram(tiny_graph())
frames = clips_for(prog.graph, 8, 3, seed=13)
dep = prog.quantize(prog.init(jax.random.PRNGKey(0)), calib=frames)
try:
    SessionPool(dep, 6, backend="ref", sharding="auto")
except ValueError as e:
    assert "does not divide" in str(e), e
else:
    raise SystemExit("auto sharding ran a 6-slot pool on 4 devices")
try:
    FleetRouter(backend="ref", ladder=(1, 2, 4), sharding="auto").register("net", dep)
except ValueError as e:
    assert "does not divide" in str(e), e
else:
    raise SystemExit("fleet accepted ladder rungs that cannot shard")
FleetRouter(backend="ref", ladder=(4, 8), sharding="auto").register("net", dep)
sharded = SessionPool(dep, 8, backend="fused", sharding="auto")
plain = SessionPool(dep, 8, backend="fused")
assert len(sharded.state.buf.sharding.device_set) == 4
for i in range(8):
    sharded.admit(f"s{i}"); plain.admit(f"s{i}")
for t in range(3):
    fr = {f"s{i}": frames[i, t] for i in range(8)}
    a, b = sharded.step(fr), plain.step(fr)
    for k in fr:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
# admit/release churn: each chip zeroes its own fresh lanes in the step
live = {f"s{i}": i for i in range(8)}
nxt = 8
for t in range(6):
    for sid in [s for s, i in live.items() if (i + t) % 3 == 0]:
        for p in (sharded, plain):
            p.release(sid)
        del live[sid]
        for p in (sharded, plain):
            p.admit(f"s{nxt}")
        live[f"s{nxt}"] = nxt
        nxt += 1
    fr = {s: frames[i % 8, t % 3] for s, i in live.items() if (i + t) % 4}
    a, b = sharded.step(fr), plain.step(fr)
    for k in fr:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    for k in live:
        assert sharded.steps_seen(k) == plain.steps_seen(k)
assert len(sharded.state.buf.sharding.device_set) == 4
assert sharded.trace_count == 1
print("SHARD-RULES-OK")
"""


def test_auto_sharding_refuses_uneven_pools_on_forced_devices():
    """On a multi-device host ``sharding="auto"`` never silently runs a pool
    on one device: an uneven pool (or fleet ladder rung) is an error, and an
    even fused pool maps its step over the devices bit-exactly."""
    repo = Path(__file__).resolve().parents[1]
    env = {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": f"{repo / 'src'}:{repo}",
        "PATH": "/usr/bin:/bin:/usr/local/bin",
    }
    proc = subprocess.run(
        [sys.executable, "-c", _SHARD_RULES_SCRIPT], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr
    assert "SHARD-RULES-OK" in proc.stdout


def test_auto_sharding_is_a_no_op_on_one_device():
    if len(jax.local_devices()) != 1:
        pytest.skip("needs a single-device host")
    from repro.serving.pool import resolve_sharding

    assert resolve_sharding("auto", 3) is None
    assert resolve_sharding(True, 8) is None
