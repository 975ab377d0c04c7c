"""Runtime self-observatory tests (nomad_tpu/profile_observe.py):
config parse validation, the thread-role classification (pinned), golden
collapsed-stack and speedscope export formats, seeded-cadence
determinism, the lock watchdog's contention timing + closure-based
violation semantics, the byte-economy ledger (rings, mirror
bucket×dtype books, the measured-per-row 1M projection), and the
/v1/agent/profile + /v1/agent/runtime + SDK + bundle surfaces over a
live agent."""

import json
import sys
import threading
import time
import urllib.error
import urllib.request
from collections import deque

import pytest

from nomad_tpu import mock, telemetry
from nomad_tpu.agent import Agent, AgentConfig
from nomad_tpu.api import ApiClient
from nomad_tpu.profile_observe import (
    ROLES,
    ProfileObserveConfig,
    RuntimeObservatory,
    classify_thread,
    collapse_frames,
    container_footprint,
    frame_label,
    rss_bytes,
    sample_schedule,
)


# -- config parse -------------------------------------------------------------


def test_config_defaults_and_parse():
    cfg = ProfileObserveConfig.parse(None)
    assert cfg.enabled is True
    assert cfg.sample_interval == 0.05
    cfg = ProfileObserveConfig.parse(
        {"enabled": False, "sample_interval": 0.1, "seed": 7,
         "max_depth": 8, "events_interval": 0}
    )
    assert cfg.enabled is False
    assert cfg.seed == 7
    assert cfg.max_depth == 8
    assert cfg.events_interval == 0.0


def test_config_parse_rejects_nonsense():
    with pytest.raises(ValueError, match="unknown profile config key"):
        ProfileObserveConfig.parse({"sample_intervall": 1.0})
    with pytest.raises(ValueError, match="must be a mapping"):
        ProfileObserveConfig.parse("fast")
    with pytest.raises(ValueError, match="sample_interval must be > 0"):
        ProfileObserveConfig.parse({"sample_interval": 0})
    with pytest.raises(ValueError, match=r"jitter must be in \[0, 1\)"):
        ProfileObserveConfig.parse({"jitter": 1.0})
    with pytest.raises(ValueError, match="max_stacks must be > 0"):
        ProfileObserveConfig.parse({"max_stacks": 0})
    with pytest.raises(ValueError, match="events_interval must be >= 0"):
        ProfileObserveConfig.parse({"events_interval": -1})


def test_file_config_validates_profile_block(tmp_path):
    """Typos in server { profile { } } fail config LOAD, not first
    use; telemetry { lock_watchdog } must be a real boolean."""
    from nomad_tpu.agent_config import load_config_file

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(
        {"server": {"enabled": True, "profile": {"sample_rate": 1}}}
    ))
    with pytest.raises(ValueError, match="unknown profile config key"):
        load_config_file(str(bad))

    bad_wd = tmp_path / "bad_wd.json"
    bad_wd.write_text(json.dumps(
        {"server": {"enabled": True},
         "telemetry": {"lock_watchdog": "yes"}}
    ))
    with pytest.raises(ValueError, match="lock_watchdog must be a bool"):
        load_config_file(str(bad_wd))

    good = tmp_path / "good.json"
    good.write_text(json.dumps(
        {"server": {"enabled": True,
                    "profile": {"sample_interval": 0.25, "seed": 9}},
         "telemetry": {"lock_watchdog": True}}
    ))
    cfg = load_config_file(str(good))
    assert cfg.server.profile == {"sample_interval": 0.25, "seed": 9}
    assert cfg.telemetry.lock_watchdog is True
    ac = AgentConfig.from_file_config(cfg)
    assert ac.profile == {"sample_interval": 0.25, "seed": 9}
    assert ac.lock_watchdog is True


# -- thread-role classification (pinned) -------------------------------------------


def test_thread_role_classification_pinned():
    """The role vocabulary is an artifact-schema contract: collapsed
    exports, speedscope profile names, and the prom role label all ride
    it. Every mapping here is deliberate."""
    cases = {
        "worker-0": "worker",
        "worker-13": "worker",
        "plan-pipeline": "pipeline-committer",
        "plan-pipeline-wait": "pipeline-committer",
        "raft-election-n1": "raft",
        "raft-leader-n1": "raft",
        "raft-compact-n1": "raft",
        "heartbeat-wheel": "heartbeat-wheel",
        "express-commit": "express-committer",
        "raft-observatory": "observer",      # before the raft- rule
        "read-observatory": "observer",
        "runtime-profiler": "observer",
        "capacity-accountant": "observer",
        "stats-emitter": "observer",
        "slo-monitor": "observer",
        "http-server": "http",
        "Thread-4 (process_request_thread)": "http",
        "MainThread": "main",
        "pytest-watcher": "other",
    }
    for name, role in cases.items():
        assert classify_thread(name) == role, name
    assert set(cases.values()) == set(ROLES)


# -- frame naming + stack collapse -------------------------------------------


def _here():
    return sys._getframe(0)


def test_frame_label_is_machine_independent():
    label = frame_label(_here())
    assert label == "test_profile_observe:_here"
    assert "/" not in label and ".py" not in label


def test_collapse_frames_root_first_and_truncates():
    stack = collapse_frames(_here(), max_depth=64)
    # Root-first: the leaf (the helper itself) is LAST.
    assert stack[-1] == "test_profile_observe:_here"
    assert stack.index(
        "test_profile_observe:"
        "test_collapse_frames_root_first_and_truncates"
    ) == len(stack) - 2
    short = collapse_frames(_here(), max_depth=3)
    assert len(short) == 3
    assert short[0] == "…"                     # root prefix folded
    assert short[-1] == "test_profile_observe:_here"  # leaf preserved


# -- seeded cadence -----------------------------------------------------------


def test_sample_schedule_deterministic_and_bounded():
    a = sample_schedule(42, 0.05, 0.2, 100)
    b = sample_schedule(42, 0.05, 0.2, 100)
    assert a == b                               # same seed, same schedule
    c = sample_schedule(43, 0.05, 0.2, 100)
    assert a != c                               # different seed decorrelates
    assert all(0.05 * 0.8 <= g <= 0.05 * 1.2 for g in a)
    # Jittered, not phase-locked: the gaps are not all identical.
    assert len(set(round(g, 9) for g in a)) > 1
    assert sample_schedule(42, 0.05, 0.0, 10) == [0.05] * 10


# -- golden export formats ----------------------------------------------------


def _synthetic_observatory(**cfg):
    obs = RuntimeObservatory(ProfileObserveConfig.parse(cfg or None))
    obs._ingest("worker", ("agent:main", "worker:run", "fit:solve"))
    obs._ingest("worker", ("agent:main", "worker:run", "fit:solve"))
    obs._ingest("worker", ("agent:main", "worker:run", "plan:submit"))
    obs._ingest("raft", ("agent:main", "raft:apply"))
    return obs


def test_golden_collapsed_output():
    """Byte-exact folded-stack text: semicolon-joined role-rooted
    frames, space, count, sorted — the flamegraph.pl input contract."""
    obs = _synthetic_observatory()
    assert obs.collapsed() == (
        "raft;agent:main;raft:apply 1\n"
        "worker;agent:main;worker:run;fit:solve 2\n"
        "worker;agent:main;worker:run;plan:submit 1\n"
    )


def test_golden_speedscope_document():
    obs = _synthetic_observatory()
    doc = obs.speedscope()
    assert doc["$schema"] == (
        "https://www.speedscope.app/file-format-schema.json")
    frames = [f["name"] for f in doc["shared"]["frames"]]
    assert frames == sorted(frames)             # deterministic table
    by_name = {p["name"]: p for p in doc["profiles"]}
    assert sorted(by_name) == ["raft", "worker"]
    worker = by_name["worker"]
    assert worker["type"] == "sampled"
    assert worker["weights"] == [2, 1]
    assert worker["endValue"] == 3
    # Every sample is indices into the shared frame table, leaf last.
    for s in worker["samples"]:
        assert frames[s[-1]] in ("fit:solve", "plan:submit")
    # The document round-trips through JSON (the download path).
    json.loads(json.dumps(doc))


def test_profiler_wall_shares_and_overflow():
    obs = _synthetic_observatory(max_stacks=2)
    # Third distinct stack exceeded max_stacks=2.
    view = obs.profile_view()["profiler"]
    assert view["distinct_stacks"] == 2
    assert view["stack_overflow"] == 1
    assert view["thread_samples"] == 4
    assert view["roles"]["worker"]["wall_share"] == 0.75
    assert view["roles"]["raft"]["wall_share"] == 0.25


def test_sample_once_sees_live_threads():
    obs = RuntimeObservatory(ProfileObserveConfig())
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name="worker-99", daemon=True)
    t.start()
    try:
        # The calling thread is excluded (in production the caller IS
        # the sampler thread), so only the worker is guaranteed.
        n = obs.sample_once()
        assert n >= 1
        view = obs.profile_view()["profiler"]
        assert view["samples"] == 1
        assert "worker" in view["roles"]
    finally:
        stop.set()
        t.join()


# -- byte-economy ledger ------------------------------------------------------


def test_rss_bytes_stdlib_only():
    rss = rss_bytes()
    assert rss["current_bytes"] > 0              # Linux container
    assert rss["peak_bytes"] >= rss["current_bytes"] // 2


def test_container_footprint_bounded_ring():
    ring = deque(({"id": "x" * 32, "n": i} for i in range(100)), maxlen=64)
    fp = container_footprint(ring)
    assert fp["entries"] == 64
    assert fp["capacity"] == 64
    assert fp["per_entry_bytes"] > 0
    assert fp["approx_bytes"] >= fp["per_entry_bytes"] * 64


def test_node_mirror_byte_ledger():
    from nomad_tpu.tpu.mirror import NodeMirror

    nodes = [mock.node() for _ in range(10)]
    ledger = NodeMirror(nodes).byte_ledger()
    assert ledger["rows"] == 10
    assert ledger["padded"] == 16                # bucket(10)
    # The named device buffers all report dtype + bytes.
    for name in ("total", "reserved_np", "sched_cap", "base_mask"):
        assert ledger["buffers"][name]["nbytes"] > 0
    assert ledger["total_bytes"] == (
        ledger["buffer_bytes"] + ledger["cache_bytes"])


def test_mirror_cache_ledger_projects_million_rows():
    from nomad_tpu.ops.binpack import bucket
    from nomad_tpu.tpu.mirror import MirrorCache, NodeMirror

    cache = MirrorCache()
    assert cache.byte_ledger()["per_row_bytes"] is None  # empty: no slope
    nodes = [mock.node() for _ in range(20)]
    cache._entries[("uid", 1, ("dc1",))] = (nodes, NodeMirror(nodes))
    ledger = cache.byte_ledger()
    assert ledger["mirrors"] == 1
    assert ledger["rows"] == 20
    assert ledger["padded_rows"] == 32
    assert "32" in ledger["by_bucket_dtype"]
    per_row = ledger["per_row_bytes"]
    assert per_row == round(ledger["total_bytes"] / 32, 2)
    # The 1M projection: measured slope × the padding bucket 1M lands in.
    assert ledger["projected_1m_rows"] == bucket(1_000_000) == 1_048_576
    assert ledger["projected_1m_bytes"] == int(per_row * 1_048_576)


def test_observatory_refresh_builds_ledger():
    ring = deque(range(50), maxlen=64)
    store = {"k": list(range(100))}
    obs = RuntimeObservatory(
        ProfileObserveConfig(),
        rings_getter=lambda: {"my_ring": ring},
        tables_getter=lambda: {"my_table": store},
    )
    obs.refresh()
    view = obs.runtime_view()
    ledger = view["bytes"]
    assert ledger["rings"]["my_ring"]["entries"] == 50
    assert ledger["tables"]["my_table"]["approx_bytes"] > 0
    assert ledger["rss"]["current_bytes"] > 0
    assert ledger["tracked_bytes"] > 0
    assert view["observer"]["polls"] == 1
    summary = obs.summary()
    assert summary["rss_mb"] > 0


# -- lock watchdog: timing + closure semantics -------------------------------


def test_lock_watchdog_times_contention():
    wd = telemetry.LockWatchdog(order=["a", "b"], sites={})
    lock = wd.watch(threading.Lock(), "a")

    holding = threading.Event()
    release = threading.Event()

    def holder():
        with lock:
            holding.set()
            release.wait(5)

    t = threading.Thread(target=holder, daemon=True)
    t.start()
    assert holding.wait(5)
    # Contended acquisition: blocks until the holder releases.
    timer = threading.Timer(0.05, release.set)
    timer.start()
    with lock:
        pass
    t.join(5)
    stats = wd.stats()
    assert stats["installed"] is False           # watch(), not install()
    row = next(r for r in stats["contention"] if r["lock"] == "a")
    assert row["acquisitions"] == 2
    assert row["contended"] == 1
    assert row["contention_rate"] == 0.5
    # We waited ~50ms for the holder: total wait and p95 both saw it.
    assert 10.0 < row["wait_total_ms"] < 5000.0
    assert row["wait_ms"]["p95"] >= 10.0
    # The holder held for ~50ms; hold books recorded both holds.
    assert row["hold_ms"]["max"] >= 10.0


def test_lock_watchdog_noncontended_fast_path_is_untimed():
    wd = telemetry.LockWatchdog(order=["a"], sites={})
    lock = wd.watch(threading.Lock(), "a")
    for _ in range(5):
        with lock:
            pass
    row = wd.stats()["contention"][0]
    assert row["acquisitions"] == 5
    assert row["contended"] == 0
    assert row["wait_total_ms"] == 0


def test_lock_watchdog_closure_violation_semantics():
    """With closure= the watchdog flags only inversions of statically
    PROVEN edges; pairs the analysis never related are recorded as
    observed edges, not violations (the whole-agent runtime-knob
    posture). Without closure= the rank comparison also flags
    unconstrained pairs (the strict single-subsystem test posture)."""
    order = ["a", "b", "c"]

    def drive(wd):
        la, lb = wd.watch(threading.Lock(), "a"), \
            wd.watch(threading.Lock(), "b")
        lc = wd.watch(threading.Lock(), "c")
        with lb:
            with la:                              # a while holding b
                pass
        with lc:
            with la:                              # a while holding c
                pass

    strict = telemetry.LockWatchdog(order=order, sites={})
    drive(strict)
    # Rank semantics: both inversions flagged.
    assert {(v.held, v.acquired) for v in strict.violations} == {
        ("b", "a"), ("c", "a")}

    informed = telemetry.LockWatchdog(
        order=order, sites={}, closure={("a", "b")})
    drive(informed)
    # Closure semantics: only b->a inverts the proven a->b edge; (a, c)
    # was never statically related, so c->a is just a new observation.
    assert [(v.held, v.acquired) for v in informed.violations] == [
        ("b", "a")]
    assert ("c", "a") in informed.observed_edges()
    with pytest.raises(AssertionError):
        informed.assert_clean()


def test_lock_watchdog_install_publishes_active_global():
    an_order = ["x"]
    wd = telemetry.LockWatchdog(order=an_order, sites={})
    assert telemetry.active_lock_watchdog() is None
    with wd:
        assert telemetry.active_lock_watchdog() is wd
        assert wd.stats()["installed"] is True
    assert telemetry.active_lock_watchdog() is None


def test_observatory_locks_view_reads_active_watchdog():
    obs = RuntimeObservatory(ProfileObserveConfig())
    assert obs.runtime_view()["locks"] == {"installed": False}
    wd = telemetry.LockWatchdog(order=["a"], sites={})
    with wd:
        lock = wd.watch(threading.Lock(), "a")
        with lock:
            pass
        view = obs.runtime_view()["locks"]
        assert view["installed"] is True
        assert view["contention"][0]["lock"] == "a"


# -- live agent e2e -----------------------------------------------------------


@pytest.fixture(scope="module")
def agent(tmp_path_factory):
    config = AgentConfig.dev()
    config.data_dir = str(tmp_path_factory.mktemp("agent"))
    config.http_port = 0
    config.scheduler_backend = "host"
    config.lock_watchdog = True
    # Fast cadences so the module's tests see samples, ledger polls and
    # a Runtime event within a second.
    config.profile = {"sample_interval": 0.02, "ledger_interval": 0.2,
                      "events_interval": 0.3}
    a = Agent(config)
    a.start()
    yield a
    a.shutdown()


def _get(agent, path):
    try:
        with urllib.request.urlopen(agent.http.addr + path,
                                    timeout=15) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _wait_for_samples(agent, n=5, timeout=15):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        obs = agent.server.runtime_observatory
        if obs.samples >= n and obs.polls >= 1:
            return
        time.sleep(0.05)
    pytest.fail(f"profiler never reached {n} samples")


def test_profile_endpoint_e2e(agent):
    _wait_for_samples(agent)
    status, body = _get(agent, "/v1/agent/profile")
    assert status == 200
    view = json.loads(body)
    prof = view["profiler"]
    assert prof["samples"] >= 5
    assert prof["schedule"]["seed"] == 42
    # The agent's own subsystem threads classified into the classification.
    assert set(prof["roles"]) <= set(ROLES)
    assert "main" in prof["roles"]
    shares = [r["wall_share"] for r in prof["roles"].values()]
    assert abs(sum(shares) - 1.0) < 0.01


def test_profile_collapsed_and_speedscope_exports(agent):
    _wait_for_samples(agent)
    status, body = _get(agent, "/v1/agent/profile?format=collapsed")
    assert status == 200
    lines = body.decode().splitlines()
    assert lines
    for line in lines:
        stack, _, count = line.rpartition(" ")
        assert int(count) >= 1
        assert stack.split(";")[0] in ROLES
    status, body = _get(agent, "/v1/agent/profile?format=speedscope")
    assert status == 200
    doc = json.loads(body)
    assert doc["$schema"].startswith("https://www.speedscope.app/")
    assert doc["profiles"]


def test_runtime_endpoint_e2e(agent):
    _wait_for_samples(agent)
    status, body = _get(agent, "/v1/agent/runtime")
    assert status == 200
    view = json.loads(body)
    # The config-gated watchdog installed at agent construction.
    assert view["locks"]["installed"] is True
    assert view["locks"]["locks_tracked"] > 0
    assert view["locks"]["violations"] == 0
    ledger = view["bytes"]
    assert "events" in ledger["rings"]
    assert ledger["rss"]["current_bytes"] > 0
    assert ledger["tracked_bytes"] > 0
    assert "mirror" in ledger


def test_runtime_prometheus_and_main_scrape(agent):
    _wait_for_samples(agent)
    status, body = _get(agent, "/v1/agent/runtime?format=prometheus")
    assert status == 200
    text = body.decode()
    assert "# TYPE nomad_profile_samples_total counter" in text
    assert "nomad_runtime_rss_bytes" in text
    assert 'nomad_profile_role_share{role="main"}' in text
    assert "nomad_lock_acquisitions_total{lock=" in text
    # Same families ride the main scrape.
    status, body = _get(agent, "/v1/agent/metrics?format=prometheus")
    assert status == 200
    main = body.decode()
    assert "nomad_profile_samples_total" in main
    assert "nomad_lock_wait_ms_total" in main
    # And the metrics JSON body carries both summaries.
    status, body = _get(agent, "/v1/agent/metrics")
    doc = json.loads(body)
    assert doc["runtime"]["samples"] >= 1
    assert doc["locks"]["installed"] is True


def test_sdk_profile_and_runtime_accessors(agent):
    _wait_for_samples(agent)
    api = ApiClient(address=agent.http.addr).agent()
    prof = api.profile()
    assert prof["profiler"]["samples"] >= 1
    runtime = api.runtime()
    assert runtime["locks"]["installed"] is True
    assert runtime["bytes"]["tracked_bytes"] > 0


def test_debug_bundle_carries_profile_and_runtime(agent):
    from nomad_tpu.bundle import BUNDLE_SECTIONS, collect

    assert "profile" in BUNDLE_SECTIONS and "runtime" in BUNDLE_SECTIONS
    _wait_for_samples(agent)
    bundle = collect(agent=agent)
    assert bundle["profile"]["profiler"]["samples"] >= 1
    assert bundle["runtime"]["bytes"]["rss"]["current_bytes"] > 0


def test_runtime_events_flow(agent):
    """Periodic RuntimeSnapshot events land on the stream — on the
    Runtime OBSERVER topic only, so canonical digests exclude them."""
    from nomad_tpu.events import OBSERVER_TOPICS

    assert "Runtime" in OBSERVER_TOPICS
    client = ApiClient(address=agent.http.addr)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        _idx, events, _trunc = client.events().list(topics=["Runtime"])
        if events:
            assert events[0]["type"] == "RuntimeSnapshot"
            assert "top_role" in events[0]["payload"]
            return
        time.sleep(0.2)
    pytest.fail("no Runtime snapshot event within 15s")


def test_profile_disabled_404(tmp_path):
    config = AgentConfig.dev()
    config.data_dir = str(tmp_path / "agent")
    config.http_port = 0
    config.scheduler_backend = "host"
    config.profile = {"enabled": False}
    a = Agent(config)
    a.start()
    try:
        assert a.server.runtime_observatory._thread is None  # never started
        status, _ = _get(a, "/v1/agent/profile")
        assert status == 404
        status, _ = _get(a, "/v1/agent/runtime")
        assert status == 404
        # The metrics body reports the observatory off, not an error.
        status, body = _get(a, "/v1/agent/metrics")
        assert status == 200
        assert json.loads(body)["runtime"] is None
    finally:
        a.shutdown()
