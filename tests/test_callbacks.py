"""train/callbacks.py unit tests — pure host, no mesh, no device step:
a stub trainer + fake clocks drive every callback path (ISSUE 2
satellite: this module previously had zero coverage).

Covers: StopAtStep, NaNGuard fail-fast vs request-stop, MetricsLogger
throughput math under a deterministic clock, the SummaryWriter
stale-scalar fix (cadence mismatch with its paired logger), and
TelemetryCallback's registry mirroring."""

import numpy as np
import pytest

from distributed_tensorflow_tpu import obs
from distributed_tensorflow_tpu.train import callbacks as cb


class StubTrainer:
    """Just the surface callbacks touch: request_stop/should_stop."""

    def __init__(self):
        self.stop_reason = None
        self.failed = False

    def request_stop(self, reason=""):
        if self.stop_reason is None:
            self.stop_reason = reason or "requested"

    @property
    def should_stop(self):
        return self.stop_reason is not None


class FakeClock:
    """Deterministic perf_counter: advances ``dt`` per call."""

    def __init__(self, dt=1.0, t0=100.0):
        self.t = t0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


class FakeTBWriter:
    def __init__(self):
        self.scalars = []  # (tag, value, step)
        self.closed = False

    def add_scalar(self, tag, value, global_step):
        self.scalars.append((tag, value, global_step))

    def flush(self):
        pass

    def close(self):
        self.closed = True


# ---------------------------------------------------------------------------
# StopAtStep / NaNGuard
# ---------------------------------------------------------------------------


def test_stop_at_step():
    t = StubTrainer()
    hook = cb.StopAtStep(last_step=3)
    for step in (1, 2):
        hook.on_step_end(t, step, {})
        assert not t.should_stop
    hook.on_step_end(t, 3, {})
    assert t.should_stop and "last_step=3" in t.stop_reason


def test_nan_guard_fail_fast_raises():
    t = StubTrainer()
    guard = cb.NaNGuard(every_n=1, fail_fast=True)
    guard.on_step_end(t, 1, {"loss": np.float32(1.0),
                             "grads_finite": np.float32(1.0)})
    with pytest.raises(FloatingPointError, match="step 2"):
        guard.on_step_end(t, 2, {"loss": np.float32(np.nan)})
    with pytest.raises(FloatingPointError):
        guard.on_step_end(t, 3, {"grads_finite": np.float32(0.0)})
    assert not t.should_stop  # fail-fast never uses the stop path


def test_nan_guard_request_stop_path():
    t = StubTrainer()
    guard = cb.NaNGuard(every_n=1, fail_fast=False)
    guard.on_step_end(t, 1, {"loss": np.float32(np.inf)})
    assert t.should_stop and "non-finite" in t.stop_reason


def test_nan_guard_cadence_gating():
    """Off-cadence steps are never inspected — the async contract."""
    t = StubTrainer()
    guard = cb.NaNGuard(every_n=10, fail_fast=True)
    guard.on_step_end(t, 5, {"loss": np.float32(np.nan)})  # not step % 10
    assert not t.should_stop
    with pytest.raises(FloatingPointError):
        guard.on_step_end(t, 10, {"loss": np.float32(np.nan)})


# ---------------------------------------------------------------------------
# MetricsLogger
# ---------------------------------------------------------------------------


def test_metrics_logger_throughput_math():
    """Fake clock: one tick per fetch → steps_per_sec is exactly
    every_n / dt, examples/sec scales by batch size."""
    clock = FakeClock(dt=2.5)
    ml = cb.MetricsLogger(every_n=5, batch_size=8, clock=clock)
    t = StubTrainer()
    ml.on_train_start(t)
    for step in range(1, 11):
        ml.on_step_end(t, step, {"loss": np.float32(1.0 / step)})
    # first fetch (step 5) has no baseline → no throughput keys
    # second fetch (step 10): 5 steps in one 2.5s clock tick
    assert ml.last_step == 10
    assert ml.last["steps_per_sec"] == pytest.approx(5 / 2.5)
    assert ml.last["examples_per_sec"] == pytest.approx(8 * 5 / 2.5)
    assert ml.last["loss"] == pytest.approx(0.1)
    assert "mfu" not in ml.last  # no model_flops given


def test_metrics_logger_reports_mfu_only_against_a_known_peak(monkeypatch):
    """The train log carries no mfu on the CPU (no peak for that device
    kind); on a kind the peak table knows, the shared helper's value."""
    def last():
        ml = cb.MetricsLogger(every_n=1, model_flops_per_step=1e9,
                              clock=FakeClock(dt=1.0))
        t = StubTrainer()
        ml.on_train_start(t)
        for step in (1, 2):
            ml.on_step_end(t, step, {"loss": np.float32(1.0)})
        return ml.last

    assert "mfu" not in last() and "steps_per_sec" in last()
    import jax

    from distributed_tensorflow_tpu.utils import flops as flops_lib

    monkeypatch.setitem(flops_lib.PEAK_FLOPS_BY_KIND, "cpu", 3e9)
    # 1e9 fwd FLOPs x3 x 1 step/s over n chips x 3e9 peak
    assert last()["mfu"] == pytest.approx(1.0 / jax.device_count())


def test_metrics_logger_cadence_and_history():
    ml = cb.MetricsLogger(every_n=3, history=True, clock=FakeClock())
    t = StubTrainer()
    ml.on_train_start(t)
    for step in range(1, 8):
        ml.on_step_end(t, step, {"loss": np.float32(step)})
    assert [h["step"] for h in ml.history] == [3, 6]
    assert ml.last_step == 6 and ml.last["loss"] == 6.0
    ml.on_train_start(t)  # restart clears staleness
    assert ml.last == {} and ml.last_step is None


# ---------------------------------------------------------------------------
# SummaryWriter stale-scalar fix
# ---------------------------------------------------------------------------


def test_summary_writer_skips_stale_logger_scalars():
    """Writer every 2, logger every 4: at steps where the logger did NOT
    fetch, the writer must read the live metrics dict, not the logger's
    old `last` (the stale-scalar bug)."""
    ml = cb.MetricsLogger(every_n=4, clock=FakeClock())
    sw = cb.SummaryWriter("unused", every_n=2, metrics_logger=ml)
    sw._writer = FakeTBWriter()  # bypass tensorboardX + chief gating
    t = StubTrainer()
    ml.on_train_start(t)
    for step in range(1, 7):
        m = {"loss": np.float32(10.0 * step)}
        ml.on_step_end(t, step, m)  # logger runs first, like in a real list
        sw.on_step_end(t, step, m)
    by_step = {s: v for (tag, v, s) in sw._writer.scalars if tag == "train/loss"}
    # steps 2 and 6: logger stale (fetched at nothing / step 4) → live value
    assert by_step[2] == pytest.approx(20.0)
    assert by_step[6] == pytest.approx(60.0)
    # step 4: cadences align → reuses the logger's freshly fetched dict
    assert by_step[4] == pytest.approx(40.0)


def test_summary_writer_reuses_aligned_logger_and_closes():
    ml = cb.MetricsLogger(every_n=2, batch_size=4, clock=FakeClock())
    sw = cb.SummaryWriter("unused", every_n=2, metrics_logger=ml)
    fake = FakeTBWriter()
    sw._writer = fake
    t = StubTrainer()
    ml.on_train_start(t)
    for step in (1, 2, 3, 4):
        m = {"loss": np.float32(step)}
        ml.on_step_end(t, step, m)
        sw.on_step_end(t, step, m)
    # aligned: the logger's derived throughput scalars get written too
    tags = {tag for (tag, _, s) in fake.scalars if s == 4}
    assert {"train/loss", "train/steps_per_sec",
            "train/examples_per_sec"} <= tags
    sw.on_train_end(t)
    assert fake.closed and sw._writer is None


# ---------------------------------------------------------------------------
# TelemetryCallback
# ---------------------------------------------------------------------------


def test_telemetry_callback_step_histogram_and_gauges():
    reg = obs.Registry()
    clock = FakeClock(dt=0.5)
    tc = cb.TelemetryCallback(registry=reg, every_n=2, clock=clock)
    t = StubTrainer()
    tc.on_train_start(t)
    for step in range(1, 6):
        tc.on_step_end(t, step, {"loss": np.float32(1.0 / step)})
    h = reg.get("train_step_seconds")
    assert h.count == 4  # first step has no baseline
    assert h.sum == pytest.approx(4 * 0.5)  # one clock tick per step
    assert reg.get("train_steps_total").value == 5
    assert reg.get("train_global_step").value == 5
    # gauges sampled at the cadence steps only — last write was step 4
    assert reg.get("train_loss").value == pytest.approx(0.25)


def test_telemetry_callback_reuses_aligned_logger_fetch():
    reg = obs.Registry()
    clock = FakeClock()
    ml = cb.MetricsLogger(every_n=2, batch_size=4, clock=clock)
    tc = cb.TelemetryCallback(registry=reg, every_n=2, metrics_logger=ml,
                              clock=clock)
    t = StubTrainer()
    ml.on_train_start(t)
    tc.on_train_start(t)
    for step in range(1, 5):
        m = {"loss": np.float32(step)}
        ml.on_step_end(t, step, m)
        tc.on_step_end(t, step, m)
    # derived scalars (steps_per_sec) only exist via the logger's dict —
    # their presence proves the aligned reuse path ran
    assert reg.get("train_steps_per_sec") is not None
    assert reg.get("train_loss").value == pytest.approx(4.0)


def test_telemetry_callback_sanitizes_metric_names():
    reg = obs.Registry()
    tc = cb.TelemetryCallback(registry=reg, every_n=1, clock=FakeClock())
    t = StubTrainer()
    tc.on_train_start(t)
    tc.on_step_end(t, 1, {"top-1/acc": np.float32(0.5)})
    assert reg.get("train_top_1_acc").value == 0.5
    # the sanitized name renders as a valid exposition line
    assert "train_top_1_acc 0.5" in obs.render(reg)


def test_telemetry_callback_defaults_to_process_registry():
    tc = cb.TelemetryCallback(every_n=1, clock=FakeClock())
    assert tc.registry is obs.default_registry()


# ---------------------------------------------------------------------------
# Watchdog abort_on_stall + fleet heartbeat seam (ISSUE 8 satellites)
# ---------------------------------------------------------------------------


class ManualClock:
    """Clock that moves only when the test moves it."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_watchdog_abort_on_stall_raises_stalled_error():
    """abort mode: the stall edge delivers a StalledError asynchronously
    into the thread that entered on_train_start, so a hung attempt dies
    classified (resilience maps it to 'stalled') instead of only
    flagging a gauge."""
    import time

    reg = obs.Registry()
    clk = ManualClock()
    wd = cb.Watchdog(budget_s=5.0, registry=reg, poll_s=0.005, clock=clk,
                     abort_on_stall=True)
    t = StubTrainer()
    wd.on_train_start(t)
    try:
        clk.t = 100.0  # hung step: way over budget, no on_step_end
        with pytest.raises(cb.StalledError):
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:  # the "hung" Python loop
                time.sleep(0.001)
            raise AssertionError("watchdog never aborted the loop")
    finally:
        wd.on_train_end(t)
    assert reg.get("train_watchdog_stalls_total").value == 1
    assert reg.get("train_watchdog_stalled").value == 1.0


def test_watchdog_default_never_aborts():
    """Detection-only default: same stall, no exception — the gauge and
    counter remain the only record."""
    import time

    reg = obs.Registry()
    clk = ManualClock()
    wd = cb.Watchdog(budget_s=5.0, registry=reg, poll_s=0.005, clock=clk)
    t = StubTrainer()
    wd.on_train_start(t)
    try:
        clk.t = 100.0
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            if reg.get("train_watchdog_stalls_total").value:
                break
            time.sleep(0.001)
        time.sleep(0.05)  # would-be delivery window: nothing may raise
    finally:
        wd.on_train_end(t)
    assert reg.get("train_watchdog_stalls_total").value == 1


def test_heartbeat_callback_beats_from_step_seam(tmp_path):
    from distributed_tensorflow_tpu.resilience import fleet as fl

    w = fl.HeartbeatWriter(str(tmp_path / "hb.json"), incarnation=1)
    hb_cb = cb.HeartbeatCallback(w, every_n=2)
    t = StubTrainer()
    hb_cb.on_train_start(t)
    hb = fl.read_heartbeat(str(tmp_path / "hb.json"))
    assert hb.phase == "train"
    seq0 = hb.seq
    hb_cb.on_step_end(t, 1, {})  # off-cadence: no write
    assert fl.read_heartbeat(str(tmp_path / "hb.json")).seq == seq0
    hb_cb.on_step_end(t, 2, {})
    hb = fl.read_heartbeat(str(tmp_path / "hb.json"))
    assert hb.seq == seq0 + 1 and hb.step == 2


def test_elastic_callback_reports_hold_as_pause():
    """A resize barrier hold is a sanctioned pause: its wall time is
    broadcast to every note_pause-aware peer (cadence meters keep
    measuring the train loop; an armed Watchdog re-arms at the
    boundary) and never booked as a step."""

    class FakeClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    clk = FakeClock()

    class HoldingClient:
        def __init__(self):
            self.polled = []

        def poll(self, step):
            self.polled.append(step)
            clk.t += 7.5  # the fleet held us for 7.5s

    class Peer(cb.Callback):
        def __init__(self):
            self.pauses = []

        def note_pause(self, seconds):
            self.pauses.append(seconds)

    client, peer = HoldingClient(), Peer()
    ecb = cb.ElasticCallback(client, clock=clk)
    t = StubTrainer()
    t.callbacks = [ecb, peer]
    ecb.on_step_end(t, 3, {})
    assert client.polled == [3]
    assert peer.pauses == [7.5]
