"""Tier-1: the bounded protocol model checker (ISSUE 18).

Three claims, each pinned:

1. the live rules hold — every model exhausts its bounded configuration
   (full profile: 3 frames, 2 crash injections at every transition)
   with ZERO counterexamples, inside the budget;
2. the checker would have caught the bugs — flipping one rule per model
   (drop the resend tail, requeue at the tail, commit the cursor, ack
   at ship time, skip the self-fence, skip the generation check) makes
   the matching invariant fire with a short (<= 20 step) printed
   counterexample trace;
3. the models cannot rot silently — the drift gate pins model legal
   sets against the dialogue reconstruction of the live tree in both
   directions (op removed from a model / op added to the transport /
   mode legal-set drift / ghost status), and the worker-adoption plane
   rides every protocol scan (PROTOCOL_COMPANIONS).
"""

import os
import subprocess
import sys

import pytest

from psana_ray_tpu.lint.core import (
    PROTOCOL_COMPANIONS,
    REPO_ROOT,
    ProjectIndex,
)
from psana_ray_tpu.lint.flow.protocol import extract_dialogue
from psana_ray_tpu.lint.model import all_models, explore, run_models
from psana_ray_tpu.lint.model.chain import ReplicationChainModel
from psana_ray_tpu.lint.model.checker import (
    ProtocolModelChecker,
    run_model_report,
)
from psana_ray_tpu.lint.model.core import render_trace
from psana_ray_tpu.lint.model.drift import NON_MODELED, check_drift
from psana_ray_tpu.lint.model.durable import DurableFloorModel
from psana_ray_tpu.lint.model.fencing import GroupFencingModel
from psana_ray_tpu.lint.model.stream import StreamModel
from psana_ray_tpu.lint.model.windowed import WindowedPutModel


@pytest.fixture(scope="module")
def dialogue():
    index = ProjectIndex(
        [os.path.join(REPO_ROOT, rel) for rel in PROTOCOL_COMPANIONS]
    )
    d = extract_dialogue(index)
    assert d is not None, "protocol companions no longer arm the dialogue"
    return d


# ---------------------------------------------------------------------------
# 1. the live rules hold
# ---------------------------------------------------------------------------

def test_full_profile_exhausts_every_model_with_zero_counterexamples():
    results = run_models("full")
    assert len(results) == 5
    for r in results:
        assert r.violation is None, render_trace(r)
        assert r.exhausted, (
            f"model {r.model.name} truncated by {r.truncated_by} — a "
            f"truncated run proves nothing"
        )
        assert r.states > 50  # a trivial state space would prove nothing
    # the budget claim: the whole fleet exhausts in seconds, not minutes —
    # held on the states explored (1,917 today; a box explores a few
    # hundred thousand a second), which a loaded machine does not move
    assert sum(r.states for r in results) < 100_000


def test_quick_profile_exhausts_too():
    # the registry entry runs this profile inside the lint budget
    for r in run_models("quick"):
        assert r.violation is None and r.exhausted
        assert r.states < 10_000  # 240 the largest today; states, not seconds


# ---------------------------------------------------------------------------
# 2. seeded mutations: every flipped rule fires its invariant
# ---------------------------------------------------------------------------

MUTATIONS = [
    # (label, mutated model, invariant that must fire)
    ("windowed-resend-tail-dropped",
     lambda: WindowedPutModel(resend_full_tail=False), "holes-never"),
    ("stream-requeue-at-tail",
     lambda: StreamModel(requeue_at_head=False), "eos-never-overtakes"),
    ("stream-window-unenforced",
     lambda: StreamModel(enforce_window=False),
     "credit-window-conservation"),
    ("stream-crash-drops-unacked",
     lambda: StreamModel(requeue_lost=False), "loss-never"),
    ("durable-commit-cursor-not-processed",
     lambda: DurableFloorModel(commit_processed_only=False),
     "committed-implies-processed"),
    ("chain-ack-at-ship-time",
     lambda: ReplicationChainModel(ack_after_logged=False),
     "ack-floor<=follower-tail"),
    ("chain-no-self-fence-behind-replica",
     lambda: ReplicationChainModel(self_fence_behind=False),
     "owner-behind-replica-self-fences"),
    ("fencing-generation-check-skipped",
     lambda: GroupFencingModel(check_generation=False),
     "stale-commit-always-fenced"),
]


@pytest.mark.parametrize(
    "label,factory,invariant", MUTATIONS, ids=[m[0] for m in MUTATIONS]
)
def test_seeded_mutation_fires_with_short_counterexample(
    label, factory, invariant
):
    result = explore(factory(), profile="full")
    assert result.violation == invariant, (
        f"{label}: expected {invariant!r}, got {result.violation!r}"
    )
    assert 0 < len(result.trace) <= 20, (
        f"{label}: counterexample must be minimal-ish, got "
        f"{len(result.trace)} steps"
    )
    rendered = render_trace(result)
    print(rendered)  # the acceptance criterion: a PRINTED opcode timeline
    assert "counterexample" in rendered and invariant in rendered
    # every step is numbered and non-empty (an opcode timeline, not a
    # state dump)
    steps = rendered.splitlines()[1:-1]
    assert len(steps) == len(result.trace)


# ---------------------------------------------------------------------------
# 3. drift gate
# ---------------------------------------------------------------------------

def test_live_tree_has_no_drift_and_models_cover_the_surface(dialogue):
    drift = list(check_drift(dialogue, all_models(), full=True))
    assert not drift, "\n".join(m for m, _h in drift)


def test_removing_an_op_from_a_model_is_a_finding(dialogue):
    models = all_models()
    victim = next(m for m in models if m.name == "windowed")
    victim.WIRE_OPS = frozenset()  # instance shadow: 'W' loses its model
    drift = list(check_drift(dialogue, models, full=True))
    assert any("_OP_PUT_SEQ" in m for m, _h in drift)


def test_unmodeled_wire_op_is_a_finding(dialogue):
    d = dict(dialogue)
    d["ops"] = dict(dialogue["ops"])
    d["ops"]["_OP_FROB"] = {"handler": "_op_frob", "handler_missing": False,
                            "emits": set()}
    drift = list(check_drift(d, all_models(), full=True))
    assert any("_OP_FROB" in m and "no protocol model" in m
               for m, _h in drift)


def test_mode_legal_set_drift_is_a_finding(dialogue):
    models = all_models()
    victim = next(m for m in models if m.name == "stream")
    # a model that still allows a second subscribe on an open stream
    victim.MODE_LEGAL_OPS = frozenset({"_OP_STREAM", "_OP_STREAM_ACK", "_OP_BYE"})
    drift = list(check_drift(dialogue, models, full=True))
    assert any("legal-op drift" in m for m, _h in drift)


def test_ghost_status_is_a_finding(dialogue):
    models = all_models()
    victim = next(m for m in models if m.name == "durable")
    victim.WIRE_STATUSES = victim.WIRE_STATUSES | {"_ST_BOGUS"}
    drift = list(check_drift(dialogue, models, full=True))
    assert any("_ST_BOGUS" in m for m, _h in drift)


def test_non_modeled_justifications_do_not_overlap_models():
    modeled = set()
    for m in all_models():
        modeled |= m.WIRE_OPS
    assert not modeled & set(NON_MODELED)
    for op, why in NON_MODELED.items():
        assert why.strip(), f"{op} needs a written justification"


def test_registry_checker_reports_mutated_fleet(monkeypatch):
    import psana_ray_tpu.lint.model.checker as checker_mod

    def mutated_fleet():
        fleet = all_models()
        return [StreamModel(requeue_at_head=False) if m.name == "stream"
                else m for m in fleet]

    monkeypatch.setattr(checker_mod, "all_models", mutated_fleet)
    index = ProjectIndex(
        [os.path.join(REPO_ROOT, rel) for rel in PROTOCOL_COMPANIONS]
    )
    findings = list(ProtocolModelChecker().run(index))
    assert any("eos-never-overtakes" in f.message
               and "counterexample" in f.message for f in findings)


# ---------------------------------------------------------------------------
# worker-adoption plane rides the protocol scans (ISSUE 18 satellite)
# ---------------------------------------------------------------------------

def test_workers_is_a_protocol_companion(dialogue):
    assert "psana_ray_tpu/transport/workers.py" in PROTOCOL_COMPANIONS
    # the adoption handshake replays ops into _on_op; every op a worker
    # must serve locally (codec/tenant hello, cluster metadata, replica
    # setup) stays a dispatched, dialogue-visible handler
    from psana_ray_tpu.transport import evloop

    assert evloop._WORKER_LOCAL_OPS  # non-empty by construction
    handlers = {rec["handler"] for rec in dialogue["ops"].values()}
    local_handlers = {
        evloop._OPS[op] for op in evloop._WORKER_LOCAL_OPS
    }
    assert local_handlers <= handlers
    # an adopted connection's replayed 'M' is a subscribe like any other:
    # dispatched on a connection that is not streamed yet, where it
    # OPENS the stream mode (inside the mode it is not legal)
    assert "_op_stream" in handlers
    assert dialogue["modes"]["stream"]["opened_by"] == "_OP_STREAM"
    assert "_OP_STREAM" not in dialogue["modes"]["stream"]["server_allowed"]


# ---------------------------------------------------------------------------
# CLI + report plumbing
# ---------------------------------------------------------------------------

def test_run_model_report_live_tree():
    results, drift = run_model_report(profile="full")
    assert not drift
    assert all(r.violation is None and r.exhausted for r in results)


def test_model_cli_exits_clean():
    proc = subprocess.run(
        [sys.executable, "-m", "psana_ray_tpu.lint", "--model"],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ok, exhausted" in proc.stdout
    assert "model: clean" in proc.stdout


def test_model_cli_flag_conflicts_are_usage_errors():
    proc = subprocess.run(
        [sys.executable, "-m", "psana_ray_tpu.lint", "--model",
         "--changed", "HEAD"],
        capture_output=True, text=True, timeout=60, cwd=REPO_ROOT,
    )
    assert proc.returncode == 2
