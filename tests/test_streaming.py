"""Multi-stream streaming engine: slot-based continuous batching over the
batched Q15 single-step kernel, with the paper's bit-exactness contract
(Sec. IV-D / Table VI) lifted to batch scale — every stream must match the
scalar C-equivalent ``core/qruntime.QRuntime`` bit for bit."""
import jax
import numpy as np
import pytest

from repro.core import fastgrnn as fg
from repro.core.qruntime import QRuntime, calibrate
from repro.core.quantization import quantize_params, QuantConfig
from repro.data import hapt
from repro.serve.streaming import (StreamingEngine, StreamingConfig,
                                   classify_windows)


def _model(low_rank=True, seed=0):
    cfg = fg.FastGRNNConfig(rank_w=2 if low_rank else None,
                            rank_u=8 if low_rank else None)
    params = fg.init_params(cfg, jax.random.PRNGKey(seed))
    return quantize_params(params, QuantConfig())


@pytest.fixture(scope="module")
def qp():
    return _model()


@pytest.fixture(scope="module")
def windows():
    return hapt.load("test", n=1100).windows


# ---------------------------------------------------------------------------
# Acceptance: >= 1024 concurrent streams, bit-identical to the scalar path
# ---------------------------------------------------------------------------

def test_1024_concurrent_streams_bit_identical(qp, windows):
    w = windows[:1024]
    eng = StreamingEngine(qp, StreamingConfig(max_slots=1024))
    for i in range(1024):
        eng.attach(f"s{i}", w[i], total_steps=len(w[i]))
    assert eng.n_active == 1024              # all resident at once
    events = eng.drain()
    by_id = {e.stream_id: e for e in events}
    assert len(by_id) == 1024

    rt = QRuntime(qp)
    ref_logits = np.stack([rt.run_window(x) for x in w])
    got_logits = np.stack([by_id[f"s{i}"].logits for i in range(1024)])
    # bit-identical logits -> bit-identical predictions (the paper's
    # cross-platform agreement contract at batch scale)
    np.testing.assert_array_equal(got_logits.view(np.int32),
                                  ref_logits.view(np.int32))
    got_pred = np.array([by_id[f"s{i}"].prediction for i in range(1024)])
    np.testing.assert_array_equal(got_pred, np.argmax(ref_logits, axis=-1))
    assert eng.stats()["stream_steps"] == 1024 * 128


def test_full_rank_bit_identical(windows):
    qp = _model(low_rank=False)
    w = windows[:40]
    eng = StreamingEngine(qp, StreamingConfig(max_slots=40))
    preds = classify_windows(eng, w)
    np.testing.assert_array_equal(preds, QRuntime(qp).predict_batch(w))


# ---------------------------------------------------------------------------
# Continuous batching: slot recycling through the pending queue
# ---------------------------------------------------------------------------

def test_slot_recycling_pending_queue(qp, windows):
    w = windows[:96]
    eng = StreamingEngine(qp, StreamingConfig(max_slots=32))
    statuses = [eng.attach(f"s{i}", w[i], total_steps=128) for i in range(96)]
    assert statuses.count("active") == 32 and statuses.count("pending") == 64
    events = eng.drain()
    preds = {e.stream_id: e.prediction for e in events}
    ref = QRuntime(qp).predict_batch(w)
    np.testing.assert_array_equal(
        np.array([preds[f"s{i}"] for i in range(96)]), ref)
    st = eng.stats()
    assert st["peak_active"] == 32           # never exceeded the slot budget
    assert st["completed"] == 96             # every queued stream finished
    assert st["ticks"] == 3 * 128            # 3 generations of 32 windows


def test_attach_respects_pending_fifo(qp, windows):
    """A new attach must not jump the queue when a slot frees up while
    earlier streams are still pending."""
    eng = StreamingEngine(qp, StreamingConfig(max_slots=1))
    eng.attach("a", windows[0], total_steps=128)
    assert eng.attach("b", windows[1], total_steps=128) == "pending"
    for _ in range(128):
        eng.step()                       # "a" finishes, slot frees
    assert eng.attach("c", windows[2], total_steps=128) == "pending"
    eng.step()                           # admission happens at tick start
    assert eng._sessions["b"].slot >= 0  # b (FIFO head) got the slot
    assert eng._sessions["c"].slot == -1


def test_attach_beyond_slots_is_pending_until_free(qp, windows):
    eng = StreamingEngine(qp, StreamingConfig(max_slots=2))
    assert eng.attach("a", windows[0], total_steps=128) == "active"
    assert eng.attach("b", windows[1], total_steps=128) == "active"
    assert eng.attach("c", windows[2], total_steps=128) == "pending"
    assert (eng.n_active, eng.n_pending) == (2, 1)
    eng.drain()
    assert (eng.n_active, eng.n_pending) == (0, 0)


# ---------------------------------------------------------------------------
# Session lifecycle
# ---------------------------------------------------------------------------

def test_detach_midwindow_emits_partial_final(qp, windows):
    eng = StreamingEngine(qp, StreamingConfig(max_slots=4))
    eng.attach("s", windows[0][:50])
    eng.drain()
    ev = eng.detach("s")
    assert ev is not None and ev.kind == "final"
    assert ev.step == 50 and ev.window_step == 50
    assert not ev.warm                        # below the 74-sample warm-up
    # the partial-window logits equal the scalar trajectory at t=50
    rt = QRuntime(qp)
    h = np.zeros(16, np.float32)
    for t in range(50):
        h = rt.step(h, windows[0][t])
    from repro.core.qruntime import _matvec
    ref = _matvec(rt._w["head_w"].T, h) + rt._head_b
    np.testing.assert_array_equal(ev.logits.view(np.int32), ref.view(np.int32))


def test_idle_slots_hold_state_bit_for_bit(qp, windows):
    """A stream fed in chunks with idle ticks in between must be
    indistinguishable from an uninterrupted replay."""
    eng = StreamingEngine(qp, StreamingConfig(max_slots=4))
    eng.attach("s", windows[0][:30], total_steps=128)
    eng.attach("busy", windows[1], total_steps=128)  # keeps ticks running
    for _ in range(70):                      # 30 real steps + 40 idle ticks
        eng.step()
    eng.feed("s", windows[0][30:])
    events = eng.drain()
    ev = [e for e in events if e.stream_id == "s"][0]
    ref = QRuntime(qp).run_window(windows[0])
    np.testing.assert_array_equal(ev.logits.view(np.int32), ref.view(np.int32))


def test_warmup_counter_and_flags(qp, windows):
    cfgs = StreamingConfig(max_slots=2, warmup_samples=74)
    eng = StreamingEngine(qp, cfgs)
    eng.attach("cold", windows[0][:40], total_steps=40)
    eng.attach("warmish", windows[1], total_steps=128)
    events = eng.drain()
    cold = [e for e in events if e.stream_id == "cold"][0]
    warm = [e for e in events if e.stream_id == "warmish"][0]
    assert cold.kind == "final" and cold.step == 40 and not cold.warm
    assert warm.kind == "window" and warm.step == 128 and warm.warm


def test_multi_window_stream_tumbling(qp, windows):
    """An open-ended stream emits one window event per 128 samples; with
    reset_on_emit each window matches an independent scalar window."""
    eng = StreamingEngine(qp, StreamingConfig(max_slots=2))
    eng.attach("s")
    for k in range(3):
        eng.feed("s", windows[k])
    events = eng.drain()
    assert [e.kind for e in events] == ["window"] * 3
    assert [e.step for e in events] == [128, 256, 384]
    rt = QRuntime(qp)
    for k, e in enumerate(events):
        np.testing.assert_array_equal(
            e.logits.view(np.int32), rt.run_window(windows[k]).view(np.int32))
    eng.detach("s")
    assert eng.n_active == 0


def test_duplicate_attach_rejected(qp):
    eng = StreamingEngine(qp, StreamingConfig(max_slots=2))
    eng.attach("s")
    with pytest.raises(ValueError):
        eng.attach("s")


# ---------------------------------------------------------------------------
# Edge cases the scheduler refactor must not break
# ---------------------------------------------------------------------------

def test_feed_after_detach_raises(qp, windows):
    eng = StreamingEngine(qp, StreamingConfig(max_slots=2))
    eng.attach("s", windows[0][:10])
    eng.drain()
    eng.detach("s")
    with pytest.raises(KeyError):
        eng.feed("s", windows[0][10:20])
    with pytest.raises(KeyError):
        eng.detach("s")                      # double detach


def test_duplicate_attach_rejected_while_pending(qp, windows):
    """A stream waiting in the pending queue still owns its id."""
    eng = StreamingEngine(qp, StreamingConfig(max_slots=1))
    eng.attach("a", windows[0], total_steps=128)
    assert eng.attach("b", windows[1], total_steps=128) == "pending"
    with pytest.raises(ValueError):
        eng.attach("b", windows[1])
    ev = eng.detach("b")                     # detach while pending: no event
    assert ev is None
    eng.attach("b", windows[1], total_steps=128)   # id reusable afterwards
    events = eng.drain()
    by_id = {e.stream_id: e for e in events}
    ref = QRuntime(qp)
    np.testing.assert_array_equal(
        by_id["b"].logits.view(np.int32),
        ref.run_window(windows[1]).view(np.int32))


def test_ring_growth_under_spill_pressure(qp, windows):
    """Feed one stream far beyond max_ring_capacity: the ring grows to its
    cap, the overflow spills to the chunk queue, drains back as the ring
    frees — and the result is still bit-identical to the scalar replay."""
    cfg = StreamingConfig(max_slots=2, ring_capacity=8, max_ring_capacity=32)
    eng = StreamingEngine(qp, cfg)
    stream = np.concatenate([windows[k] for k in range(3)])   # 384 samples
    eng.attach("s")
    eng.feed("s", stream)                    # 384 >> 32: deep backlog
    st = eng.stats()
    assert st["ring_capacity"] == 32         # grew 8 -> 32 and capped
    assert st["ring_spills"] >= 1            # overflow hit the spill queue
    events = eng.drain()
    assert [e.kind for e in events] == ["window"] * 3
    rt = QRuntime(qp)
    for k, e in enumerate(events):
        np.testing.assert_array_equal(
            e.logits.view(np.int32), rt.run_window(windows[k]).view(np.int32))
    assert eng.stats()["stream_steps"] == 384


class _RingModel:
    """Plain model of one slot's sample ring: each sample is written alone
    at ``tail % cap``; a packet that does not fit grows the ring (doubling,
    up to the cap) and re-bases the cursors, and what still does not fit
    queues behind, in order, until a step frees room."""

    def __init__(self, cap, max_cap):
        self.cap, self.max_cap = max(8, min(cap, max_cap)), max_cap
        self.cells = [None] * self.cap
        self.head = self.tail = self.spills = 0
        self.spill = []

    def live(self):
        return [self.cells[(self.head + i) % self.cap]
                for i in range(self.tail - self.head)]

    def feed(self, samples):
        if self.spill:
            self.spill.extend(samples)
            return
        need = self.tail - self.head + len(samples)
        if need > self.cap and self.cap < self.max_cap:
            cap = self.cap
            while cap < need:
                cap *= 2
            live, self.cap = self.live(), min(cap, self.max_cap)
            self.cells = live + [None] * (self.cap - len(live))
            self.head, self.tail = 0, len(live)
        rest = list(samples)
        while rest and self.tail - self.head < self.cap:
            self.cells[self.tail % self.cap] = rest.pop(0)
            self.tail += 1
        if rest:
            self.spill, self.spills = rest, self.spills + 1

    def step(self):
        if self.tail > self.head:
            self.head += 1
        while self.spill and self.tail - self.head < self.cap:
            self.cells[self.tail % self.cap] = self.spill.pop(0)
            self.tail += 1


@pytest.mark.parametrize("cap,max_cap,start,packets", [
    (16, 16, 3, [1]),               # 1 sample, no wrap
    (16, 16, 15, [1, 1]),           # 1 sample at the last cell, then wraps
    (32, 32, 4, [25]),              # the fleet's packet, no wrap
    (32, 32, 20, [25]),             # the fleet's packet across the end
    (16, 16, 9, [15]),              # cap - 1, wraps
    (16, 16, 0, [16]),              # cap at offset 0: fills the ring
    (16, 16, 7, [16]),              # cap across the end
    (16, 64, 7, [17]),              # cap + 1: the ring grows
    (16, 16, 7, [17, 5]),           # cap + 1 at the cap: spill, FIFO behind
    (8, 32, 5, [64, 3]),            # 2 * max_ring_capacity: grow, then spill
], ids=["1", "1-wrap", "25", "25-wrap", "cap-1", "cap", "cap-wrap",
        "cap+1-grow", "cap+1-spill", "2max-grow-spill"])
def test_ring_write_matches_fifo_model(qp, cap, max_cap, start, packets):
    """Every packet lands in the ring (and its spill) exactly as a
    one-sample-at-a-time FIFO would put it, on three neighbouring slots,
    through wraps, growth, spill and the drain that empties it."""
    eng = StreamingEngine(qp, StreamingConfig(
        max_slots=3, ring_capacity=cap, max_ring_capacity=max_cap))
    d = eng.kernel.input_dim
    sids = ["a", "b", "c"]
    for sid in sids:
        eng.attach(sid)
    models = [_RingModel(cap, max_cap) for _ in sids]
    fed = [[] for _ in sids]
    consumed = 0

    def feed(k):
        for j, sid in enumerate(sids):    # distinct values per slot
            n0 = len(fed[j])
            x = (np.arange(n0 * d, (n0 + k) * d, dtype=np.float32)
                 .reshape(k, d) + 1e5 * j)
            eng.feed(sid, x)
            models[j].feed(list(x))
            fed[j] += list(x)

    def step(n):
        nonlocal consumed
        for _ in range(n):
            eng.step()
            for m in models:
                m.step()
        consumed = min(consumed + n, len(fed[0]))

    def check():
        for j, sid in enumerate(sids):
            slot, m = eng._sessions[sid].slot, models[j]
            assert (eng._cap, eng._head[slot], eng._tail[slot]) == \
                (m.cap, m.head, m.tail)
            n = m.tail - m.head
            ring = eng._ring[(m.head + np.arange(n)) % m.cap, slot]
            want = np.array(fed[j][consumed:], np.float32).reshape(-1, d)
            np.testing.assert_array_equal(ring, want[:n])
            np.testing.assert_array_equal(
                ring, np.array(m.live(), np.float32).reshape(-1, d))
            np.testing.assert_array_equal(
                eng.snapshot_stream(sid).samples, want)
        assert eng.stats()["ring_spills"] == sum(m.spills for m in models)

    feed(start)
    step(start)
    check()
    for k in packets:
        feed(k)
        check()
    while consumed < len(fed[0]):           # drain, spill included
        step(min(7, len(fed[0]) - consumed))
        check()
    assert not eng._spill


def test_drain_with_empty_pending_queue(qp, windows):
    eng = StreamingEngine(qp, StreamingConfig(max_slots=2))
    assert eng.drain() == []                 # nothing attached at all
    eng.attach("idle")                       # attached but never fed
    assert eng.drain() == []
    assert eng.n_active == 1 and eng.n_pending == 0


def test_scheduler_counters_surfaced_in_stats(qp, windows):
    eng = StreamingEngine(qp, StreamingConfig(max_slots=2))
    for i in range(4):
        eng.attach(f"s{i}", windows[i], total_steps=128)
    eng.drain()
    st = eng.stats()
    sched = st["scheduler"]
    assert sched["admissions"] == 4
    assert sched["recycles"] == 2            # generation 2 reused slots
    assert sched["spills"] == 2              # two streams had to queue
    assert sched["completed"] == 4
    assert sched["occupancy"] == 0.0         # everything finished
    assert st["completed"] == 4 and st["peak_active"] == 2


# ---------------------------------------------------------------------------
# Activation-storage modes (Table V) ride through the batched path
# ---------------------------------------------------------------------------

def test_calibrated_act_quant_matches_scalar(qp, windows):
    rt = QRuntime(qp)
    scales = calibrate(rt, windows[:5])
    eng = StreamingEngine(qp, StreamingConfig(max_slots=8), act_scales=scales)
    preds = classify_windows(eng, windows[:8])
    ref = QRuntime(qp, act_scales=scales).predict_batch(windows[:8])
    np.testing.assert_array_equal(preds, ref)


def test_naive_act_quant_matches_scalar(qp, windows):
    eng = StreamingEngine(qp, StreamingConfig(max_slots=8), naive_acts=True)
    preds = classify_windows(eng, windows[:8])
    ref = QRuntime(qp, naive_acts=True).predict_batch(windows[:8])
    np.testing.assert_array_equal(preds, ref)


# ---------------------------------------------------------------------------
# Fast backends: same predictions, relaxed bit contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jit", "pallas"])
def test_fast_backends_agree_on_predictions(qp, windows, backend):
    n = 48 if backend == "jit" else 16
    eng = StreamingEngine(
        qp, StreamingConfig(max_slots=16, backend=backend))
    preds = classify_windows(eng, windows[:n])
    ref = QRuntime(qp).predict_batch(windows[:n])
    assert float(np.mean(preds == ref)) == 1.0


def test_float_params_quantized_on_entry(windows):
    """The engine accepts a float param pytree and applies Appendix-B PTQ."""
    cfg = fg.FastGRNNConfig(rank_w=2, rank_u=8)
    params = fg.init_params(cfg, jax.random.PRNGKey(1))
    eng = StreamingEngine(params, StreamingConfig(max_slots=4))
    preds = classify_windows(eng, windows[:4])
    qp = quantize_params(params, QuantConfig())
    np.testing.assert_array_equal(preds, QRuntime(qp).predict_batch(windows[:4]))
