"""The assembled SFX capability, end to end: stream -> PeakNet -> CXI.

The reference's packaging names this as the mission ("Save PeakNet
inference results to CXI", reference ``setup.py:11``) but ships no code
for it; these tests define the behavior for psana_ray_tpu.sfx. The e2e
test is an ORACLE test: synthetic events carry planted peak ground truth,
a small PeakNet trains briefly on the self-supervised label recipe, and
the CXI file written by the pipeline must recover the planted peaks
within tolerance — proving the whole chain (transport, batcher, jitted
segmentation+extraction, panel->raw coordinate fold, HDF5 layout,
cursor) preserves the physics, not just the plumbing."""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DET = "smoke_a"
SEED = 5
FEATURES = (8, 16)
EVAL_RUN = 2  # training uses run=1 events 0..319; run 2 reseeds every event
N_EVENTS = 12


def _train_and_export(out_dir: str):
    """The documented train->serve recipe (examples/train_peaknet.py at
    smoke scale): 80 steps of focal-loss training on self-derived labels
    (calibrated intensity > 50), norm='batch', then the exact
    export_serving_params fold. Measured on this recipe: recall ~0.73,
    precision ~0.99 against planted truth at threshold 0.5 / min_dist 2."""
    import optax
    from flax.core import meta

    from psana_ray_tpu.models import (
        PeakNetUNetTPU,
        export_serving_params,
        host_init,
        panels_to_nhwc,
    )
    from psana_ray_tpu.models.losses import masked_sigmoid_focal
    from psana_ray_tpu.parallel.steps import TrainState, make_train_step
    from psana_ray_tpu.sources import SyntheticSource

    src = SyntheticSource(num_events=1, detector_name=DET, seed=SEED)
    p, h, w = src.spec.frame_shape
    b, n_steps = 4, 80
    model = PeakNetUNetTPU(features=FEATURES, norm="batch", s2d=2)
    variables = meta.unbox(host_init(model, (b * p, h, w, 1)))
    opt = optax.adam(3e-3)
    opt_state = jax.jit(opt.init)({"params": variables["params"]})
    state = TrainState(variables, opt_state, jnp.zeros((), jnp.int32))
    step = make_train_step(
        model, opt,
        lambda lg, aux: masked_sigmoid_focal(lg, aux[0], aux[1], alpha=0.9),
    )

    @jax.jit
    def prepare(frames):
        x = panels_to_nhwc(frames, mode="batch")
        return x, (x > 50.0).astype(jnp.float32)

    for s in range(n_steps):
        frames = np.stack([src.event(s * b + j)[0] for j in range(b)])
        x, tg = prepare(jnp.asarray(frames))
        state, _ = step(state, x, (tg, jnp.ones((b * p,), jnp.uint8)))
    export_serving_params(state.variables, out_dir)


@pytest.fixture(scope="module")
def serving_ckpt(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("sfx") / "serving")
    _train_and_export(d)
    return d


def _truth_raw_coords(idx: int, panel_h: int) -> np.ndarray:
    """Planted truth for event ``idx`` in the pipeline's unassembled raw
    layout: rows (0, y_raw, x_raw, amplitude)."""
    from psana_ray_tpu.sources import SyntheticSource

    src = SyntheticSource(run=EVAL_RUN, num_events=1, detector_name=DET, seed=SEED)
    _, _, truth = src.event_with_truth(idx)
    t = truth.copy()
    t[:, 1] = t[:, 0] * panel_h + t[:, 1]  # y_raw = panel*H + cy
    t[:, 0] = 0
    return t


def _score_cxi(path: str, panel_h: int):
    """Greedy-match every CXI event's peaks against its planted truth."""
    from psana_ray_tpu.models.peaks import peak_metrics, read_cxi_peaks

    n, x, y, inten, event_idx = read_cxi_peaks(path)
    pred_yx = np.stack([y, x], axis=-1)
    truth = [_truth_raw_coords(int(e), panel_h) for e in event_idx]
    return peak_metrics(pred_yx, n, truth, tolerance=3.0, min_amplitude=100.0), set(
        int(e) for e in event_idx
    )


def test_infer_s2d_reads_checkpoint(serving_ckpt):
    from psana_ray_tpu.checkpoint import load_params
    from psana_ray_tpu.sfx import infer_s2d

    v = load_params(serving_ckpt)
    assert infer_s2d(v.get("params", v)) == 2
    with pytest.raises(ValueError, match="logits"):
        infer_s2d({"not": "a tree"})


def test_infer_features_reads_checkpoint(serving_ckpt):
    from psana_ray_tpu.checkpoint import load_params
    from psana_ray_tpu.sfx import infer_features

    v = load_params(serving_ckpt)
    assert infer_features(v.get("params", v)) == FEATURES
    with pytest.raises(ValueError, match="ConvBlock_0"):
        infer_features({"not": "a tree"})


def test_features_mismatch_refused(serving_ckpt, tmp_path):
    """An explicit features tuple that contradicts the checkpoint is an
    early clear refusal, not a shape error deep in the first apply."""
    from psana_ray_tpu.checkpoint import load_params
    from psana_ray_tpu.cxi import CxiWriter
    from psana_ray_tpu.sfx import SfxPipeline

    with CxiWriter(str(tmp_path / "x.cxi")) as w:
        with pytest.raises(ValueError, match="does not match the checkpoint"):
            SfxPipeline(load_params(serving_ckpt), w, features=(4, 8))


def test_e2e_stream_to_cxi_recovers_planted_peaks(serving_ckpt, tmp_path):
    """The full library-surface pipeline: ProducerRuntime streaming
    held-out synthetic events -> queue -> SfxPipeline -> CXI file whose
    peak lists match the planted ground truth; cursor advances to the
    stream's end."""
    from psana_ray_tpu.checkpoint import StreamCursor, load_params
    from psana_ray_tpu.config import PipelineConfig, SourceConfig
    from psana_ray_tpu.models.peaks import CxiWriter
    from psana_ray_tpu.producer import ProducerRuntime
    from psana_ray_tpu.sfx import SfxConfig, SfxPipeline
    from psana_ray_tpu.sources.base import DETECTORS
    from psana_ray_tpu.transport.addressing import open_queue

    cfg = PipelineConfig(
        source=SourceConfig(
            exp="synthetic", run=EVAL_RUN, num_events=N_EVENTS,
            detector_name=DET, seed=SEED,
        )
    )
    ProducerRuntime(cfg).run(block=False)
    queue = open_queue(cfg.transport)

    cxi = str(tmp_path / "run.cxi")
    cursor_path = str(tmp_path / "run.cursor")
    cursor = StreamCursor(stride=1)
    variables = load_params(serving_ckpt)
    with CxiWriter(cxi, max_peaks=64) as writer:
        pipe = SfxPipeline(
            variables, writer, features=FEATURES,
            config=SfxConfig(batch_size=4),
        )
        n = pipe.run(queue, cursor=cursor, cursor_path=cursor_path)
    assert n == N_EVENTS
    assert pipe.n_peaks > 0

    h = DETECTORS[DET].height
    m, events = _score_cxi(cxi, h)
    assert events == set(range(N_EVENTS))
    # the physics bar: planted peaks recovered through the WHOLE pipeline
    assert m["recall"] >= 0.6, m
    assert m["precision"] >= 0.8, m

    # resume watermark is durable and complete
    resumed = StreamCursor.load(cursor_path)
    assert resumed.resume_point(0) == N_EVENTS


def _cxi_bytes(path: str) -> dict:
    """Every dataset of a CXI file, as bytes."""
    import h5py

    rows = {}
    with h5py.File(path, "r") as f:
        f.visititems(
            lambda name, obj: rows.update({name: obj[()].tobytes()})
            if isinstance(obj, h5py.Dataset) else None
        )
    return rows


def _stream_to_cxi_datasets(serving_ckpt, path: str) -> dict:
    """A short stream through ``SfxPipeline`` with every panel row over a
    small cap (threshold 0.02, 4 peaks a panel: the cut falls where the
    scores crowd); every dataset of the CXI file as bytes."""
    from psana_ray_tpu.checkpoint import load_params
    from psana_ray_tpu.config import PipelineConfig, SourceConfig
    from psana_ray_tpu.models.peaks import CxiWriter
    from psana_ray_tpu.producer import ProducerRuntime
    from psana_ray_tpu.sfx import SfxConfig, SfxPipeline
    from psana_ray_tpu.transport.addressing import open_queue

    cfg = PipelineConfig(
        source=SourceConfig(
            exp="synthetic", run=EVAL_RUN, num_events=8, detector_name=DET, seed=SEED,
        )
    )
    ProducerRuntime(cfg).run(block=False)
    with CxiWriter(path, max_peaks=64) as writer:
        pipe = SfxPipeline(
            load_params(serving_ckpt), writer, features=FEATURES,
            config=SfxConfig(batch_size=4, max_peaks=4, peak_threshold=0.02, min_distance=2),
        )
        assert pipe.run(open_queue(cfg.transport)) == 8
    return _cxi_bytes(path)


def test_cxi_rows_equal_the_dense_find_peaks(serving_ckpt, tmp_path, monkeypatch):
    """The ORDER of peaks is part of the file: ``find_peaks`` runs TopK over
    one candidate per block and, in the step, reads the head's logits
    PACKED (``s2d`` the checkpoint's own, the full-resolution map never
    formed: PR 41); what lands in the CXI datasets is byte for byte what
    the dense form (tests/dense_peaks.py) writes when patched in over the
    same logits unshuffled."""
    from dense_peaks import dense_find_peaks

    import psana_ray_tpu.models.peaks as peaks
    from psana_ray_tpu.cxi import read_cxi_peaks
    from psana_ray_tpu.models.unet_tpu import depth_to_space
    from psana_ray_tpu.sources.base import DETECTORS

    handed = []

    def dense_on_the_unshuffled_map(logits, s2d=1, **kw):
        handed.append((logits.shape, s2d))
        return dense_find_peaks(depth_to_space(logits, s2d), **kw)

    blocks = str(tmp_path / "blocks.cxi")
    got = _stream_to_cxi_datasets(serving_ckpt, blocks)
    monkeypatch.setattr(peaks, "find_peaks", dense_on_the_unshuffled_map)  # looked up per trace
    want = _stream_to_cxi_datasets(serving_ckpt, str(tmp_path / "dense.cxi"))
    spec = DETECTORS[DET]
    # the step hands over what the head computes: [rows, H/2, W/2, 4], s2d 2
    assert handed and set(handed) == {((4 * spec.panels, spec.height // 2, spec.width // 2, 4), 2)}
    assert got.keys() == want.keys() and len(got) >= 7
    for name in want:
        assert got[name] == want[name], name
    n, *_ = read_cxi_peaks(blocks)
    assert (n == 4 * DETECTORS[DET].panels).all()  # every panel row at its cap


def test_competing_sfx_consumers_partition_and_merge(serving_ckpt, tmp_path):
    """The pod deployment shape: TWO SfxPipeline consumers compete on ONE
    queue (the reference's consumer-side DP, SURVEY §2 row 22), each
    writing its own CXI file; the dynamic partition must be disjoint and
    exhaustive, both consumers must terminate on the shared EOS (the
    batcher re-enqueues sibling markers), and `merge_cxi` must reassemble
    the full run from the per-consumer files."""
    from psana_ray_tpu.checkpoint import load_params
    from psana_ray_tpu.config import PipelineConfig, SourceConfig, TransportConfig
    from psana_ray_tpu.cxi import merge_cxi, read_cxi_peaks
    from psana_ray_tpu.models.peaks import CxiWriter
    from psana_ray_tpu.producer import ProducerRuntime
    from psana_ray_tpu.sfx import SfxConfig, SfxPipeline
    from psana_ray_tpu.transport.addressing import open_queue

    cfg = PipelineConfig(
        source=SourceConfig(
            exp="synthetic", run=EVAL_RUN, num_events=N_EVENTS,
            detector_name=DET, seed=SEED,
        ),
        # one EOS marker per expected consumer (reference parity,
        # producer.py:124-125) — without this the first consumer to pop
        # the single marker ends the stream and its sibling waits forever
        transport=TransportConfig(num_consumers=2),
    )
    ProducerRuntime(cfg).run(block=False)
    variables = load_params(serving_ckpt)
    paths = [str(tmp_path / f"consumer{i}.cxi") for i in range(2)]
    counts = [None, None]
    errors = []

    def consume(i):
        try:
            queue = open_queue(cfg.transport)
            with CxiWriter(paths[i], max_peaks=64) as writer:
                pipe = SfxPipeline(
                    variables, writer, features=FEATURES,
                    config=SfxConfig(batch_size=2),
                )
                counts[i] = pipe.run(queue)
        except BaseException as e:  # surfaced in the main thread
            errors.append((i, e))

    # daemon: if EOS fan-out regresses, a consumer blocks forever in
    # batches_from_queue — the join-timeout assertion must then fail the
    # test rather than the stuck non-daemon thread hanging pytest exit
    threads = [
        threading.Thread(target=consume, args=(i,), daemon=True) for i in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "competing consumer failed to terminate on EOS"
    assert not errors, errors

    per_consumer = [set(int(e) for e in read_cxi_peaks(p)[4]) for p in paths]
    assert per_consumer[0] & per_consumer[1] == set(), "duplicate delivery"
    assert per_consumer[0] | per_consumer[1] == set(range(N_EVENTS))
    assert sum(counts) == N_EVENTS

    merged = str(tmp_path / "merged.cxi")
    assert merge_cxi(paths, merged) == N_EVENTS
    n, *_rest, event_idx = read_cxi_peaks(merged)
    assert len(n) == N_EVENTS
    assert [int(e) for e in event_idx] == list(range(N_EVENTS))


@pytest.mark.slow
def test_sfx_cli_subprocess_over_shm(serving_ckpt, tmp_path):
    """The installed-CLI surface: a real `python -m psana_ray_tpu.sfx`
    process drains an shm ring fed by this process and writes the CXI
    file — the runbook's operator path."""
    from psana_ray_tpu.records import EndOfStream, FrameRecord
    from psana_ray_tpu.sources import SyntheticSource
    from psana_ray_tpu.transport.shm_ring import ShmRingBuffer, native_available

    if not native_available():
        pytest.skip("native shm ring unavailable")

    name = f"sfx_test_{os.getpid()}"
    cxi = str(tmp_path / "cli.cxi")
    src = SyntheticSource(
        run=EVAL_RUN, num_events=8, detector_name=DET, seed=SEED,
    )
    frame_bytes = int(np.prod(src.spec.frame_shape)) * 4
    ring = ShmRingBuffer.create(name, maxsize=16, slot_bytes=frame_bytes + 4096)
    try:
        def produce():
            for idx, data, energy in src.iter_indexed_events():
                while not ring.put(FrameRecord(0, idx, data, energy)):
                    time.sleep(0.002)
            assert ring.put_wait(EndOfStream(total_events=8), timeout=60.0)

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [
                sys.executable, "-m", "psana_ray_tpu.sfx",
                "--address", f"shm://{name}",
                "--serving_params", serving_ckpt,
                "--features", ",".join(str(f) for f in FEATURES),
                "--mode", "quality",
                "--output", cxi,
                "--batch", "4",
            ],
            capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
        )
        t.join(timeout=60)
        assert out.returncode == 0, out.stderr[-2000:]
        from psana_ray_tpu.models.peaks import read_cxi_peaks

        n, *_ , event_idx = read_cxi_peaks(cxi)
        assert len(n) == 8
        assert set(int(e) for e in event_idx) == set(range(8))
    finally:
        ring.destroy()


def test_max_events_bound_drains_in_flight_batch(serving_ckpt, tmp_path):
    """--max_events stops the run near the bound; the one-deep pipelined
    loop may overshoot by at most one extra batch (which MUST still be
    written — it was dispatched, and the producer will not re-send it),
    and every written event is covered by the saved cursor."""
    from psana_ray_tpu.checkpoint import StreamCursor, load_params
    from psana_ray_tpu.config import PipelineConfig, SourceConfig
    from psana_ray_tpu.models.peaks import CxiWriter, read_cxi_peaks
    from psana_ray_tpu.producer import ProducerRuntime
    from psana_ray_tpu.sfx import SfxConfig, SfxPipeline
    from psana_ray_tpu.transport.addressing import open_queue

    cfg = PipelineConfig(
        source=SourceConfig(
            exp="synthetic", run=EVAL_RUN, num_events=N_EVENTS,
            detector_name=DET, seed=SEED,
        )
    )
    ProducerRuntime(cfg).run(block=False)
    queue = open_queue(cfg.transport)
    cxi = str(tmp_path / "bounded.cxi")
    cursor_path = str(tmp_path / "bounded.cursor")
    cursor = StreamCursor(stride=1)
    with CxiWriter(cxi, max_peaks=32) as writer:
        pipe = SfxPipeline(
            load_params(serving_ckpt), writer, features=FEATURES,
            config=SfxConfig(batch_size=2),
        )
        n = pipe.run(
            queue, cursor=cursor, cursor_path=cursor_path, max_events=5,
        )
    # bound reached, overshoot bounded by batch granularity + one in flight
    assert 5 <= n <= 5 + 2 * 2 - 1
    n_rows, *_ , event_idx = read_cxi_peaks(cxi)
    assert len(n_rows) == n
    # the durable watermark covers exactly what was written (contiguous
    # prefix: single shard, in-order stream)
    assert StreamCursor.load(cursor_path).resume_point(0) == n
    if hasattr(queue, "close"):
        queue.close()


def test_cxi_writer_append_mode(tmp_path):
    """Crash-resume must never truncate durably-written events: mode='a'
    re-opens and appends after the last event; a max_peaks mismatch (row
    width baked into the file) is refused."""
    from psana_ray_tpu.models.peaks import CxiWriter, PeakSet, read_cxi_peaks

    path = str(tmp_path / "resume.cxi")
    mk = lambda i: PeakSet(  # noqa: E731
        event_idx=i, shard_rank=0,
        y=np.array([1.0 * i]), x=np.array([2.0 * i]),
        intensity=np.array([0.9]), photon_energy=9.5,
    )
    with CxiWriter(path, max_peaks=16) as w:
        w.append([mk(0), mk(1), mk(2)])
    with CxiWriter(path, max_peaks=16, mode="a") as w:
        assert w.n_events == 3  # picked up where the crashed run stopped
        w.append([mk(3), mk(4)])
    n, x, y, inten, event_idx = read_cxi_peaks(path)
    assert list(event_idx) == [0, 1, 2, 3, 4]
    assert y[3][0] == 3.0  # pre-crash rows intact, post-resume rows real
    with pytest.raises(ValueError, match="max_peaks"):
        CxiWriter(path, max_peaks=32, mode="a")


def test_fresh_run_refuses_existing_output(serving_ckpt, tmp_path):
    """A fresh (non-resume) CLI run must not silently truncate an
    existing CXI file."""
    from psana_ray_tpu.sfx import main

    out = tmp_path / "exists.cxi"
    out.write_bytes(b"not empty")
    rc = main([
        "--serving_params", serving_ckpt,
        "--output", str(out),
    ])
    assert rc == 1
    assert out.read_bytes() == b"not empty"  # untouched


def test_merge_cxi_dedupes_at_least_once_replays(tmp_path):
    """The resume companion: merging a crashed run's file with its
    resumed run's file drops (shard_rank, event_idx) duplicates, keeping
    the resumed run's version, sorted deterministically."""
    from psana_ray_tpu.models.peaks import (
        CxiWriter,
        PeakSet,
        merge_cxi,
        read_cxi_peaksets,
    )

    mk = lambda i, v: PeakSet(  # noqa: E731
        event_idx=i, shard_rank=0,
        y=np.array([v], np.float32), x=np.array([v], np.float32),
        intensity=np.array([0.5], np.float32), photon_energy=9.0,
    )
    run1, run2 = str(tmp_path / "r1.cxi"), str(tmp_path / "r2.cxi")
    with CxiWriter(run1, max_peaks=8) as w:
        w.append([mk(0, 10.0), mk(1, 11.0), mk(2, 12.0)])
    with CxiWriter(run2, max_peaks=8) as w:  # resume re-processed 2, added 3-4
        w.append([mk(2, 99.0), mk(3, 13.0), mk(4, 14.0)])

    out = str(tmp_path / "merged.cxi")
    n = merge_cxi([run1, run2], out)  # max_peaks derived from inputs
    assert n == 5
    sets = read_cxi_peaksets(out)
    assert [p.event_idx for p in sets] == [0, 1, 2, 3, 4]
    assert sets[2].y[0] == 99.0  # resumed run superseded the crashed one
    assert sets[0].photon_energy == pytest.approx(9.0)  # keV round trip

    # no-clobber: an existing output is refused, never truncated
    with pytest.raises(ValueError, match="refusing to overwrite"):
        merge_cxi([run1, run2], out)
    # lossless: a narrower explicit max_peaks is refused, not truncated
    with pytest.raises(ValueError, match="lossless"):
        merge_cxi([run1, run2], str(tmp_path / "narrow.cxi"), max_peaks=4)

    out2 = str(tmp_path / "merged_first.cxi")
    merge_cxi([run1, run2], out2, keep="first")
    assert read_cxi_peaksets(out2)[2].y[0] == 12.0  # first kept instead


def test_merge_cxi_streaming_chunks_and_bad_inputs(tmp_path):
    """chunk_events smaller than the event count must not change the
    result (the two-pass streaming path); a missing input path and a
    foreign HDF5 layout are clean CLI errors, not tracebacks."""
    import h5py

    from psana_ray_tpu.models.peaks import (
        CxiWriter, PeakSet, merge_cxi, merge_cxi_main, read_cxi_peaksets,
    )

    mk = lambda i, v: PeakSet(  # noqa: E731
        event_idx=i, shard_rank=i % 2,
        y=np.array([v], np.float32), x=np.array([v], np.float32),
        intensity=np.array([0.5], np.float32), photon_energy=9.0,
    )
    src = str(tmp_path / "src.cxi")
    with CxiWriter(src, max_peaks=8) as w:
        w.append([mk(i, float(i)) for i in range(7)])
    out = str(tmp_path / "chunked.cxi")
    assert merge_cxi([src], out, chunk_events=2) == 7
    sets = read_cxi_peaksets(out)
    # sorted by (shard_rank, event_idx): evens (rank 0) then odds (rank 1)
    assert [p.event_idx for p in sets] == [0, 2, 4, 6, 1, 3, 5]
    assert all(p.y[0] == p.event_idx for p in sets)

    rc = merge_cxi_main([str(tmp_path / "nope.cxi"), "--output",
                         str(tmp_path / "x.cxi")])
    assert rc == 1  # missing input: clean error, not an h5py traceback

    foreign = str(tmp_path / "foreign.h5")
    with h5py.File(foreign, "w") as f:
        f.create_dataset("d", data=[1])
    rc = merge_cxi_main([foreign, "--output", str(tmp_path / "y.cxi")])
    assert rc == 1  # foreign layout: refused with the ValueError message


def test_merge_cxi_interleaved_files_chunked(tmp_path):
    """Winners interleave across input files within one output slab (the
    batched pass-2 read groups rows by file and must reassemble them in
    sorted key order): odd events in one file, even in the other, with a
    chunk smaller than either file's contribution."""
    from psana_ray_tpu.cxi import CxiWriter, PeakSet, merge_cxi, read_cxi_peaksets

    mk = lambda i: PeakSet(  # noqa: E731
        event_idx=i, shard_rank=0,
        y=np.array([float(i)], np.float32), x=np.array([0.0], np.float32),
        intensity=np.array([1.0], np.float32), photon_energy=8.0,
    )
    evens, odds = str(tmp_path / "e.cxi"), str(tmp_path / "o.cxi")
    with CxiWriter(evens, max_peaks=4) as w:
        w.append([mk(i) for i in range(0, 20, 2)])
    with CxiWriter(odds, max_peaks=4) as w:
        w.append([mk(i) for i in range(1, 20, 2)])
    out = str(tmp_path / "m.cxi")
    assert merge_cxi([evens, odds], out, chunk_events=3) == 20
    sets = read_cxi_peaksets(out)
    assert [p.event_idx for p in sets] == list(range(20))
    assert all(p.y[0] == p.event_idx for p in sets)  # rows from right file

    with pytest.raises(ValueError, match="chunk_events"):
        merge_cxi([evens], str(tmp_path / "z.cxi"), chunk_events=0)


def test_merge_cxi_cli(tmp_path):
    from psana_ray_tpu.models.peaks import CxiWriter, PeakSet, merge_cxi_main, read_cxi_peaks

    p = str(tmp_path / "a.cxi")
    with CxiWriter(p, max_peaks=4) as w:
        w.append([PeakSet(event_idx=7, shard_rank=1,
                          y=np.array([1.0], np.float32),
                          x=np.array([2.0], np.float32),
                          intensity=np.array([0.9], np.float32))])
    out = str(tmp_path / "m.cxi")
    assert merge_cxi_main([p, p, "--output", out]) == 0
    n, *_, ev = read_cxi_peaks(out)
    assert len(n) == 1 and int(ev[0]) == 7  # self-merge dedupes


def test_mode_mismatch_refused(serving_ckpt, tmp_path):
    """--mode throughput against an s2d=2 checkpoint must refuse (the
    operating mode is a property of the trained tree)."""
    from psana_ray_tpu.sfx import main

    rc = main([
        "--serving_params", serving_ckpt,
        "--mode", "throughput",
        "--output", str(tmp_path / "x.cxi"),
    ])
    assert rc == 1


def test_cxi_append_refuses_foreign_hdf5(tmp_path):
    """mode='a' on a valid HDF5 file that is not a CxiWriter file must
    raise a clear ValueError (and release the handle), not a KeyError."""
    import h5py

    from psana_ray_tpu.models.peaks import CxiWriter

    path = str(tmp_path / "foreign.h5")
    with h5py.File(path, "w") as f:
        f.create_dataset("something_else", data=[1, 2, 3])
    with pytest.raises(ValueError, match="foreign"):
        CxiWriter(path, mode="a")
    # handle released: the file can be reopened for writing immediately
    with h5py.File(path, "r+") as f:
        assert "something_else" in f


def test_raw_stream_with_on_device_calibration(serving_ckpt, tmp_path):
    """The --calib_npz serving shape: the stream carries RAW ADUs and the
    compiled step runs fused calibration in FRONT of the net. Pins the
    gain convention — the npz gain is ABSOLUTE (ADUs/photon, i.e.
    spec.adu_gain * relative map): with it, peaks recover the planted
    truth like the calib-stream path; the relative map alone would feed
    the net 35x-hot frames (the examples/train_peaknet.py trap)."""
    from psana_ray_tpu.checkpoint import load_params
    from psana_ray_tpu.config import PipelineConfig, SourceConfig
    from psana_ray_tpu.models.peaks import CxiWriter
    from psana_ray_tpu.producer import ProducerRuntime
    from psana_ray_tpu.sfx import SfxConfig, SfxPipeline
    from psana_ray_tpu.sources import SyntheticSource
    from psana_ray_tpu.sources.base import DETECTORS
    from psana_ray_tpu.transport.addressing import open_queue

    # calibration constants from the SAME run as the stream: pedestal /
    # gain / mask are seeded per (exp, run, seed), so a default run=1
    # source would calibrate run-2 frames with mismatched constants
    src = SyntheticSource(run=EVAL_RUN, num_events=1, detector_name=DET, seed=SEED)
    calib = (
        src.pedestal(),
        src.spec.adu_gain * src.gain_map(),  # ABSOLUTE gain -> photons out
        src.create_bad_pixel_mask(),
    )

    cfg = PipelineConfig(
        source=SourceConfig(
            exp="synthetic", run=EVAL_RUN, num_events=N_EVENTS,
            detector_name=DET, seed=SEED, mode="raw",
        )
    )
    ProducerRuntime(cfg).run(block=False)
    queue = open_queue(cfg.transport)

    cxi = str(tmp_path / "raw.cxi")
    variables = load_params(serving_ckpt)
    with CxiWriter(cxi, max_peaks=64) as writer:
        pipe = SfxPipeline(
            variables, writer, calib=calib, config=SfxConfig(batch_size=4),
        )
        n = pipe.run(queue)
    assert n == N_EVENTS

    h = DETECTORS[DET].height
    m, events = _score_cxi(cxi, h)
    assert events == set(range(N_EVENTS))
    # same physics bar as the calib-stream e2e: the on-device chain must
    # hand the net the same photon-scale distribution it trained on
    assert m["recall"] >= 0.6, m
    assert m["precision"] >= 0.8, m


# ---------------------------------------------------------------------------
# the early drain (ISSUE 35): ``run`` drains a finished batch between two
# turns of the batcher, on the one thread it has
# ---------------------------------------------------------------------------

SMALL = (2, 32, 128)  # panels, height, width
B = 4


@pytest.fixture(scope="module")
def small_variables():
    from flax.core import meta

    from psana_ray_tpu.models import PeakNetUNetTPU, host_init

    model = PeakNetUNetTPU(features=(8, 16), norm="frozen", s2d=2)
    return meta.unbox(host_init(model, (1, SMALL[1], SMALL[2], 1)))


def _small_frame(i):
    from psana_ray_tpu.records import FrameRecord

    data = np.random.default_rng(i).integers(0, 4000, SMALL).astype(np.uint16)
    return FrameRecord(0, i, data, 9.5)


def _small_pipe(small_variables, writer, **cfg):
    from psana_ray_tpu.sfx import SfxConfig, SfxPipeline

    calib = (np.zeros(SMALL, np.float32), np.ones(SMALL, np.float32), np.ones(SMALL, np.uint8))
    return SfxPipeline(
        small_variables, writer, calib=calib,
        config=SfxConfig(batch_size=B, max_peaks=4, peak_threshold=0.02, **cfg),
    )


class _RowsWriter:
    """Stands where the CxiWriter does: every appended row's event, in
    file order."""

    max_peaks = 64

    def __init__(self):
        self.events = []

    def append(self, sets):
        self.events += [s.event_idx for s in sets]


class _StubResult:
    """One output of a stub step. It answers ``is_ready`` as its batch's
    gate says; ``device_get`` reads it back either way (the blocking
    drain)."""

    def __init__(self, arr, gate):
        self._arr, self._gate = arr, gate

    def is_ready(self):
        return self._gate["ready"]

    def __array__(self, dtype=None, copy=None):
        return self._arr


class _ScriptedQueue:
    """A transport whose every pop is scripted. An int is that many fresh
    frames (0: a starved poll); ``"ready"`` makes every dispatched step's
    result ready; a callable is called; both then go on to the next entry.
    ``"eos"``, or the script's end, is the end of the stream."""

    def __init__(self, script, make_ready):
        self.script = list(script)
        self.sent = 0
        self.timeouts = []  # of every pop
        self._make_ready = make_ready

    def get_batch(self, n, timeout=None):
        from psana_ray_tpu.records import EndOfStream

        self.timeouts.append(timeout)
        while self.script:
            act = self.script.pop(0)
            if act == "ready":
                self._make_ready()
            elif callable(act):
                act()
            elif act == "eos":
                break
            else:
                assert act <= n
                out = [_small_frame(self.sent + j) for j in range(act)]
                self.sent += act
                return out
        self.script = []
        return [EndOfStream(total_events=self.sent)]


class _Scripted:
    """``SfxPipeline.run`` over a scripted queue, every ``dispatch``,
    ``drain`` and call of the batcher's hook in ``log``, in order:
    ``("dispatch", i)``, ``("drain", i)`` ... ``("appended", i)`` with i
    the batch's place in dispatch order, ``"hook>"`` ... ``"<hook"``."""

    def __init__(self, small_variables, monkeypatch, script, *, stub=True,
                 ready_at_once=False, writer=None, **cfg):
        import psana_ray_tpu.infeed.batcher as batcher_mod

        self.log = log = []
        self.handles = handles = []
        self.staged = []  # frames put ahead of each dispatch, in dispatch order
        self.served = served = []  # (the batch's own rows, the rows its step was given), likewise
        self.writer = writer if writer is not None else _RowsWriter()
        self.pipe = pipe = _small_pipe(small_variables, self.writer, **cfg)
        gates = []

        def stub_step(frames):
            served.append(np.stack([np.asarray(f) for f in frames]))
            rows = len(frames) * SMALL[0]
            gates.append(gate := {"ready": ready_at_once})
            return tuple(
                _StubResult(a, gate)
                for a in (np.zeros((rows, 1, 2), np.int32), np.full((rows, 1), 0.9, np.float32),
                          np.ones(rows, np.int32))
            )

        def make_ready():
            for gate in gates:
                gate["ready"] = True
            if not stub and handles:
                jax.block_until_ready(handles[-1][0])

        real_step = pipe._step

        def watched_step(frames):
            served.append(np.stack([np.asarray(f) for f in frames]))
            return real_step(frames)

        monkeypatch.setattr(pipe, "_step", stub_step if stub else watched_step)
        real_dispatch, real_drain, real_batches = (
            pipe.dispatch, pipe.drain, batcher_mod.batches_from_queue,
        )

        def dispatch(batch, staged=()):
            log.append(("dispatch", len(handles)))
            self.staged.append(len(staged))
            handles.append(real_dispatch(batch, staged))
            served[-1] = (batch.frames.copy(), served[-1])
            return handles[-1]

        def drain(pending, **kw):
            i = next(j for j, h in enumerate(handles) if h is pending)
            log.append(("drain", i))
            n = real_drain(pending, **kw)
            log.append(("appended", i))
            return n

        def batches(*a, between_turns=None, **kw):
            def hook():
                log.append("hook>")
                try:
                    return between_turns()
                finally:
                    log.append("<hook")

            return real_batches(*a, between_turns=hook, **kw)

        monkeypatch.setattr(pipe, "dispatch", dispatch)
        monkeypatch.setattr(pipe, "drain", drain)
        monkeypatch.setattr(batcher_mod, "batches_from_queue", batches)  # run looks it up per call
        self.queue = _ScriptedQueue(script, make_ready)

    def run(self, **kw):
        kw.setdefault("poll_interval_s", 0.001)
        return self.pipe.run(self.queue, **kw)

    @property
    def calls(self):
        """The ``dispatch`` / ``drain`` calls alone, in order."""
        return [e for e in self.log if e[0] in ("dispatch", "drain")]

    def every_step_was_given_its_own_batch(self):
        """Staged or not, the rows a step was given are its batch's, in
        the batch's order (a tail's padding included)."""
        return bool(self.served) and all(
            np.array_equal(own, given) for own, given in self.served)

    def drains_inside_the_hook(self):
        inside, n = False, 0
        for e in self.log:
            if e == "hook>":
                inside = True
            elif e == "<hook":
                inside = False
            elif inside and e[0] == "drain":
                n += 1
        return n


def _one_deep(n_batches):
    """The dispatch / drain calls of the one-deep loop: each batch is
    drained right after the next one is launched, the last at the end."""
    calls = []
    for i in range(n_batches):
        calls.append(("dispatch", i))
        if i:
            calls.append(("drain", i - 1))
    return calls + [("drain", n_batches - 1)]


SPARSE = {
    # the step ends while the next batch is filling; seen when a frame lands
    "seen-at-a-frame": ([B, "ready", 1, B - 1, "ready", 2, B - 2, "ready", 1, B - 1], False),
    # seen at an empty poll of a silent stream; not ready: polled, not drained
    "seen-at-an-empty-poll": ([B, 0, 0, "ready", 0, B, 0, "ready", 0, 0, B, "ready", 0, B], False),
    # ready by the time the launch returns: the first turn after it drains
    "ready-at-launch": ([B, 1, B - 1, 0, B], True),
}


@pytest.mark.parametrize("name", sorted(SPARSE))
def test_sparse_stream_is_appended_before_the_next_dispatch(small_variables, monkeypatch, name):
    script, ready_at_once = SPARSE[name]
    s = _Scripted(small_variables, monkeypatch, script, ready_at_once=ready_at_once)
    n = s.run()
    n_batches = n // B
    assert n == s.queue.sent and n_batches >= 3
    for i in range(n_batches - 1):
        assert s.log.index(("appended", i)) < s.log.index(("dispatch", i + 1)), s.log
    # each handle once, in order; all but the last inside the hook (the
    # stream ended on the last one's launch)
    assert [e[1] for e in s.calls if e[0] == "drain"] == list(range(n_batches))
    assert s.drains_inside_the_hook() == n_batches - 1
    assert s.pipe.metrics.drained_ahead.count == n_batches - 1
    assert s.pipe.metrics.batches.count == n_batches
    assert s.writer.events == list(range(n))


DENSE = {
    # the queue always holds a batch: the hook is not even asked, though
    # every result is ready at once
    "a-batch-a-pop": dict(script=[B] * 6, ready_at_once=True, hook_calls=0),
    # it always holds frames, half a batch a pop: asked every other turn,
    # and the step has never ended by then
    "half-a-batch-a-pop": dict(script=[B // 2] * 12, ready_at_once=False, hook_calls=6),
}


@pytest.mark.parametrize("name", sorted(DENSE))
def test_dense_stream_runs_the_one_deep_schedule(small_variables, monkeypatch, name):
    case = DENSE[name]
    s = _Scripted(small_variables, monkeypatch, case["script"],
                  ready_at_once=case["ready_at_once"])
    n = s.run()
    assert n == sum(case["script"])
    assert s.calls == _one_deep(n // B)
    assert s.log.count("hook>") == case["hook_calls"]
    assert s.drains_inside_the_hook() == 0
    assert s.pipe.metrics.drained_ahead.count == 0
    assert s.writer.events == list(range(n))


def test_a_stream_that_turns_dense_falls_back_to_the_one_deep_schedule(
    small_variables, monkeypatch
):
    # one loop, one drain: ahead while the device outruns the frames, after
    # the next launch once it does not, each handle once either way
    s = _Scripted(small_variables, monkeypatch, [B, "ready", 0, B, B, B, 0, "ready", 0, B])
    assert s.run() == 5 * B
    assert s.calls == [
        ("dispatch", 0), ("drain", 0), ("dispatch", 1), ("dispatch", 2), ("drain", 1),
        ("dispatch", 3), ("drain", 2), ("drain", 3), ("dispatch", 4), ("drain", 4),
    ]
    assert s.pipe.metrics.drained_ahead.count == 2
    assert s.writer.events == list(range(5 * B))


def test_while_a_step_runs_the_pop_is_asked_to_end_every_millisecond(small_variables, monkeypatch):
    # so the step's end is seen within one, not at the next frame's arrival;
    # with nothing dispatched and undrained the caller's interval stands
    s = _Scripted(small_variables, monkeypatch, [B, 0, 0, "ready", 0, 0, 0, B, 0, "ready", 0, 0])
    assert s.run(poll_interval_s=0.25) == 2 * B
    #      B     0      0      ready,0  0     0     B     0      ready,0  0     eos
    assert s.queue.timeouts == [
        0.25, 0.001, 0.001, 0.001, 0.25, 0.25, 0.25, 0.001, 0.001, 0.25, 0.25,
    ]
    assert s.pipe.metrics.drained_ahead.count == 2


@pytest.mark.parametrize("gap_s", [0.0, 0.002], ids=["as-fast-as-the-ring-accepts", "paced"])
def test_soak_over_a_real_ring_loses_and_doubles_nothing(small_variables, gap_s):
    """Several hundred batches through a 16-slot ``shm://`` ring and the
    real compiled step, under this test's own deadline: ``run`` returns at
    the end of the stream with every frame's row once, in order. (On an
    idle machine the first stream drains nothing ahead and the paced one
    nearly every batch; which it was is not asserted, a loaded machine
    mixes them.)"""
    from psana_ray_tpu.records import EndOfStream
    from psana_ray_tpu.transport.shm_ring import ShmRingBuffer, native_available

    if not native_available():
        pytest.skip("native shm ring unavailable")
    n = 300 * B
    frames = [_small_frame(i) for i in range(16)]
    ring = ShmRingBuffer.create(f"sfx_soak_{os.getpid()}_{time.monotonic_ns()}", maxsize=16,
                                slot_bytes=64 * 1024)
    stop, late = threading.Event(), threading.Event()
    deadline = threading.Timer(240.0, lambda: (late.set(), stop.set()))
    deadline.daemon = True

    def produce():
        from psana_ray_tpu.records import FrameRecord

        for i in range(n):
            rec = FrameRecord(0, i, frames[i % 16].panels, 9.5)
            while not ring.put_wait(rec, timeout=1.0):
                if stop.is_set():
                    return
            if gap_s:
                time.sleep(gap_s)
        ring.put_wait(EndOfStream(total_events=n), timeout=60.0)

    try:
        pipe = _small_pipe(small_variables, _RowsWriter())
        feeder = threading.Thread(target=produce, daemon=True)
        deadline.start()
        feeder.start()
        wrote = pipe.run(ring, poll_interval_s=0.001, stop=stop)
        deadline.cancel()
        feeder.join(timeout=30)
        assert not late.is_set(), f"run was still going at the deadline, {wrote} of {n} rows in"
        assert wrote == n and not feeder.is_alive()
        assert pipe.writer.events == list(range(n))  # none lost, none doubled, in order
        m = pipe.metrics
        assert m.batches.count == n // B and 0 <= m.drained_ahead.count <= n // B
    finally:
        deadline.cancel()
        stop.set()
        ring.destroy()


@pytest.mark.parametrize(
    "script,staged",
    [
        ([B, "ready", 1, B - 1, 0, "ready", 0, B, "ready", 2], [0, 1, 0, 2]),
        ([B] * 3 + [2], [0, 0, 0, 2]),
        ([2] * 7, [2, 2, 2, 2]),
        # one frame a turn: all but the frame that fills the batch went ahead;
        # of the tail its three real rows, its padding at the launch
        ([1] * (2 * B + 3), [B - 1, B - 1, 3]),
        ([1, "ready", 0] * (2 * B + 1), [B - 1, B - 1, 1]),
        ([B - 1, B, 0, B, 1], [B - 1, B - 1, 0]),  # rows behind a batch go at the next starved poll, or not at all
    ],
    ids=["sparse", "dense", "dense-half-pops", "trickle", "trickle-drained-ahead", "straddling"],
)
def test_cxi_file_is_byte_for_byte_the_serial_loops(
    small_variables, monkeypatch, tmp_path, script, staged
):
    """Whatever share of a batch went to the device ahead of its launch,
    the file is ``process_batch``'s over the same frames, bit for bit."""
    from psana_ray_tpu.infeed.batcher import FrameBatcher
    from psana_ray_tpu.models.peaks import CxiWriter

    n = sum(a for a in script if isinstance(a, int))
    with CxiWriter(str(tmp_path / "run.cxi"), max_peaks=64) as writer:
        s = _Scripted(small_variables, monkeypatch, script, stub=False, writer=writer)
        assert s.run() == n
    assert s.staged == staged and s.every_step_was_given_its_own_batch()
    assert s.pipe.metrics.frames_staged_ahead.count == sum(staged)
    with CxiWriter(str(tmp_path / "serial.cxi"), max_peaks=64) as writer:
        pipe = _small_pipe(small_variables, writer)
        batcher = FrameBatcher(B)
        for i in range(n):
            if (batch := batcher.push(_small_frame(i))) is not None:
                pipe.process_batch(batch)
        if (tail := batcher.flush()) is not None:
            pipe.process_batch(tail)
    got, want = _cxi_bytes(str(tmp_path / "run.cxi")), _cxi_bytes(str(tmp_path / "serial.cxi"))
    assert got.keys() == want.keys() and len(want) >= 7
    for name in want:
        assert got[name] == want[name], name
    assert len(want["entry_1/result_1/nPeaks"]) > 0


class _FailingWriter(_RowsWriter):
    """The second append fails: before its rows land, or after."""

    def __init__(self, rows_land):
        super().__init__()
        self.rows_land = rows_land
        self.calls = 0

    def append(self, sets):
        self.calls += 1
        if self.calls == 2:
            if self.rows_land:
                super().append(sets)
            raise OSError("disk full")
        super().append(sets)


@pytest.mark.parametrize("rows_land", [False, True], ids=["before-the-rows", "after-the-rows"])
@pytest.mark.parametrize(
    "script",
    [[B, "ready", 0, B, "ready", 0, B], [B, B, B, B]],
    ids=["in-the-hook", "after-the-next-launch"],
)
def test_a_drain_that_raises_surfaces_from_run_and_nothing_is_written_twice(
    small_variables, monkeypatch, tmp_path, script, rows_land
):
    from psana_ray_tpu.checkpoint import StreamCursor

    s = _Scripted(small_variables, monkeypatch, script, writer=_FailingWriter(rows_land))
    path = str(tmp_path / "run.cursor")
    with pytest.raises(OSError, match="disk full"):
        s.run(cursor=StreamCursor(stride=1), cursor_path=path, cursor_save_every=1)
    drained = [e[1] for e in s.calls if e[0] == "drain"]
    assert drained == [0, 1]  # the failed handle is not drained again, nothing after it is
    assert s.writer.calls == 2
    assert s.writer.events == list(range(2 * B if rows_land else B))  # each row once
    # the watermark covers what was written and never runs ahead of it
    assert StreamCursor.load(path).resume_point(0) == B == s.pipe.n_events


def test_stop_set_during_an_early_drain_ends_the_run_with_every_dispatch_written(
    small_variables, monkeypatch
):
    stop = threading.Event()

    class StopsAtTheFirstAppend(_RowsWriter):
        def append(self, sets):
            super().append(sets)
            stop.set()

    s = _Scripted(small_variables, monkeypatch, [B, "ready", 0, B, B],
                  writer=StopsAtTheFirstAppend())
    assert s.run(stop=stop) == B
    assert s.calls == [("dispatch", 0), ("drain", 0)] and s.drains_inside_the_hook() == 1
    assert s.writer.events == list(range(B))
    assert s.queue.script == [B, B]  # nothing more was popped


def test_max_events_reached_inside_the_hook_ends_the_run_there(
    small_variables, monkeypatch, tmp_path
):
    from psana_ray_tpu.checkpoint import StreamCursor

    s = _Scripted(small_variables, monkeypatch,
                  [B, "ready", 0, B, "ready", 0, B, "ready", 0, B])
    path = str(tmp_path / "bounded.cursor")
    n = s.run(cursor=StreamCursor(stride=1), cursor_path=path, max_events=B + 1)
    # the bound is crossed by the second batch, drained in the hook; the
    # one-deep loop's overshoot (2 * batch - 1) is not passed
    assert n == 2 * B <= (B + 1) + 2 * B - 1
    assert s.calls == [("dispatch", 0), ("drain", 0), ("dispatch", 1), ("drain", 1)]
    assert s.drains_inside_the_hook() == 2
    assert s.writer.events == list(range(n))
    assert StreamCursor.load(path).resume_point(0) == n
    assert s.queue.script  # the rest of the stream was left in the transport


@pytest.mark.parametrize(
    "script,dense",
    [([B] * 12, True), (sum(([B, "ready", 0] for _ in range(12)), []), False)],
    ids=["dense", "sparse"],
)
def test_drained_ahead_is_counted_and_exported(small_variables, monkeypatch, script, dense):
    from test_obs import parse_prometheus

    from psana_ray_tpu.obs import MetricsRegistry

    s = _Scripted(small_variables, monkeypatch, script)
    assert s.run() == 12 * B
    snap = s.pipe.metrics.snapshot()
    assert snap["batches_total"] == 12
    if dense:
        assert snap["drained_ahead_total"] == 0
    else:
        assert snap["drained_ahead_total"] >= 0.9 * snap["batches_total"]
    reg = MetricsRegistry()
    reg.register("sfx", s.pipe.metrics)
    text = reg.render_prometheus()
    samples = parse_prometheus(text)
    assert samples[("psana_ray_drained_ahead_total", 'source="sfx"')] == snap["drained_ahead_total"]
    assert samples[("psana_ray_batches_total", 'source="sfx"')] == 12.0
    assert "# TYPE psana_ray_drained_ahead_total counter" in text


# ---------------------------------------------------------------------------
# frames go to the device as they land (ISSUE 43): what was staged belongs
# to the batcher's current arena, and to nothing else
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "script,per_batch,puts",
    [([1] * (6 * B), B - 1, 6 * (B - 1)), ([B] * 6, 0, 0), ([B // 2] * 12, B // 2, 6)],
    ids=["trickle", "whole-batches", "half-a-batch-a-pop"],
)
def test_frames_staged_ahead_are_counted_and_exported(
    small_variables, monkeypatch, script, per_batch, puts
):
    from test_obs import parse_prometheus

    from psana_ray_tpu.obs import MetricsRegistry

    s = _Scripted(small_variables, monkeypatch, script)
    assert s.run() == 6 * B
    assert s.staged == [per_batch] * 6 and s.every_step_was_given_its_own_batch()
    snap = s.pipe.metrics.snapshot()
    assert snap["frames_total"] == 6 * B
    assert snap["frames_staged_ahead_total"] == 6 * per_batch
    # the staging has a phase of its own, one observation a turn that staged
    put_ahead = s.pipe.metrics.stages.stat("put_ahead")
    assert (put_ahead.count if put_ahead else 0) == puts
    reg = MetricsRegistry()
    reg.register("sfx", s.pipe.metrics)
    text = reg.render_prometheus()
    samples = parse_prometheus(text)
    assert samples[("psana_ray_frames_staged_ahead_total", 'source="sfx"')] == 6 * per_batch
    assert "# TYPE psana_ray_frames_staged_ahead_total counter" in text


def test_the_put_ahead_phase_is_a_span_with_its_frames_and_bytes(small_variables, monkeypatch, tmp_path):
    import json

    from psana_ray_tpu.obs.tracing import TRACER

    TRACER.configure(str(tmp_path), sample_every=1, process="t")
    try:
        s = _Scripted(small_variables, monkeypatch, [1, 2, 1, B])
        assert s.run() == 2 * B
        path = TRACER.spool_path
    finally:
        TRACER.close()
    rows = [json.loads(line) for line in open(path) if line.strip()]
    spans = [r for r in rows if r["t"] == "s" and r["n"] == "stage.put_ahead"]
    frame_bytes = int(np.prod(SMALL)) * 2
    assert [(r["k"], r["y"]) for r in spans] == [(1, frame_bytes), (2, 2 * frame_bytes)]
    # each lies between a turn's copy and the next turn's wait, before its batch's launch
    launch = next(r for r in rows if r["t"] == "s" and r["n"] == "stage.launch")
    assert all(r["b"] <= launch["a"] for r in spans)


def test_a_stop_drops_what_was_staged_with_its_arena(small_variables, monkeypatch):
    stop = threading.Event()
    s = _Scripted(small_variables, monkeypatch, [B, 1, 1, stop.set, 0, B])
    assert s.run(stop=stop) == B  # the two staged frames are abandoned with their arena
    assert s.staged == [0] and s.writer.events == list(range(B))
    # the same pipeline over what the producer sends again: nothing of the
    # abandoned arena is served, under any batch's ids
    s.queue = _ScriptedQueue([1] * (2 * B), lambda: None)
    assert s.run() == 2 * B
    assert s.staged == [0, B - 1, B - 1] and s.every_step_was_given_its_own_batch()
    assert s.writer.events == list(range(B)) + list(range(2 * B))


@pytest.mark.parametrize("rows_land", [False, True], ids=["before-the-rows", "after-the-rows"])
def test_a_drain_that_raises_between_two_staged_frames_serves_nothing_twice(
    small_variables, monkeypatch, rows_land
):
    # batch 1's early drain fails while two frames of batch 2 are on the device
    s = _Scripted(small_variables, monkeypatch, [B, "ready", 0, B, 1, 1, "ready", 1, 1, B],
                  writer=_FailingWriter(rows_land))
    with pytest.raises(OSError, match="disk full"):
        s.run()
    assert [e[1] for e in s.calls if e[0] == "drain"] == [0, 1]
    assert s.staged == [0, 0]  # batch 2 was never launched
    assert s.writer.events == list(range(2 * B if rows_land else B))  # each row once
    assert s.every_step_was_given_its_own_batch()
    s.queue = _ScriptedQueue([1] * B + [B], lambda: None)
    assert s.run() == 2 * B  # a fresh stream: fresh arenas, nothing carried over
    assert s.staged == [0, 0, B - 1, 0] and s.every_step_was_given_its_own_batch()


def test_a_trickled_stream_compiles_nothing_after_the_warm_up(small_variables):
    """The benchmark warms the loop through ``process_batch`` alone; a
    stream that stages 1, 2 or 3 frames a turn, or none, and ends in a
    padded tail must then find its one program compiled."""
    from psana_ray_tpu.infeed.batcher import Batch

    pipe = _small_pipe(small_variables, _RowsWriter())
    full = np.stack([_small_frame(i).panels for i in range(B)])
    for first in (0, B):
        pipe.process_batch(Batch(
            frames=full, valid=np.ones(B, np.uint8), shard_rank=np.full(B, -1, np.int32),
            event_idx=np.arange(first, first + B, dtype=np.int64),
            photon_energy=np.zeros(B, np.float32),
        ))
    compiled = pipe._jit_step._cache_size()
    assert compiled == 1
    queue = _ScriptedQueue([1, 1, 1, 1, 2, 2, 3, 1, B, 1, 0, 3, 2], lambda: None)
    assert pipe.run(queue, poll_interval_s=0.001) == queue.sent == 4 * B + 6
    assert pipe.metrics.frames_staged_ahead.count == 3 + 2 + 3 + 0 + 1 + 2
    assert pipe._jit_step._cache_size() == compiled  # no miss: the tuple of B frames, as warmed
