"""What the twelve ``tests/test_decoder*.py`` share (imported as
``test_manifest_entries`` is): the seeded inputs, the loud weights, the
embedded batch, a holder's share, the patches a kernel's tests compute under
and the cell's rehearsal; and :class:`Kit`, which makes the three things
nearly every "against the reference" test needs ONCE a key within a worker:
the parameters, the program's side, the clean reference's side.

The rule of the cache: an artifact is a pure function of its key. The key
holds the file's ``mapping`` overrides (order-free), the seed, the batch, the
tiles and the NAMES of the patches it is computed under; the kit enters those
patches itself, in its own ``with``, and refuses to compute while a patch the
key does not name is held by the asking test. What comes back is a fresh
container around the cached arrays, so a test may rebind a layer's entry.
The kit knows no model: each file hands it its ``mapping``, its
``benchmark.reference`` module and, where they differ, its way of calling
``decoder.trunk`` and the reference."""

import contextlib
import dataclasses
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from psana_ray_tpu.models import decoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATCHES, PROMPT = 56, 8  # 64 tokens a sequence
HIGHEST = "highest"  # the patch every comparison in float32 names: jax.default_matmul_precision
F32_PRODUCTS = (HIGHEST, "float32_products")  # ... and with a kernel's products in float32 beside it
ACTIVE = []  # the labels of the patches held right now, by a fixture or by the kit


def inputs(seed, batch=1, patches=PATCHES, prompt=PROMPT):
    rng = np.random.default_rng(seed)
    frames = jnp.asarray(rng.standard_normal((batch, patches, 64)), jnp.float32)
    return frames, jnp.asarray(rng.integers(0, 256, prompt))


def loud(params, by=5.0, keep=()):
    """The same tree with its 0.02-matrices scaled up, so that every part of a
    layer moves its output by more than a rounding; the entries whose name
    starts with one of ``keep`` (taps and the like, of order 1 as drawn) stay."""
    def up(path, a):
        name = getattr(path[-1], "key", "")
        return a * by if a.ndim >= 2 and not name.startswith(tuple(keep)) else a

    return jax.tree_util.tree_map_with_path(up, params)


def embedded(params, patches, ids, cfg=None):
    """The batch's embedded rows, one sequence after the other (under ``cfg``'s
    embedding multiplier and stream type where a file passes it)."""
    scale = (cfg.embedding_multiplier, cfg.stream_dtype) if cfg is not None else ()
    return jnp.concatenate([decoder.embed(params, frame, ids, *scale) for frame in patches])


def share_of(params, first, count):
    """The tree a holder of experts ``first .. first + count`` has."""
    held = ("w_gate", "w_up", "w_down")
    return {**params, "layers": [
        {k: (v[first:first + count] if k in held and v.ndim == 3 else v) for k, v in p.items()}
        for p in params["layers"]]}


def apart(a, b):
    return float(jnp.sqrt(jnp.mean((a - b) ** 2)) / jnp.sqrt(jnp.mean(b ** 2)))


# ---------------------------------------------------------------------------
# patches: a module's attribute replaced for a while, the traces that hold the old one dropped
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def patched(label, module, name, value, *cached):
    """``module.name = value`` inside the block. ``cached`` are the jitted
    functions whose traces hold what they were traced with: their caches go
    before and after, so neither side's programs reach the other."""
    old = getattr(module, name)
    for fn in cached:
        fn.clear_cache()
    setattr(module, name, value)
    ACTIVE.append(label)
    try:
        yield
    finally:
        ACTIVE.remove(label)
        setattr(module, name, old)
        for fn in cached:
            fn.clear_cache()


def _float32_mm(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32), (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


def float32_products(module, *cached):
    """A kernel module's products (``_mm``) in float32, so that what is left
    between the kernel and the recurrence is its FORM alone."""
    return patched("float32_products", module, "_mm", _float32_mm, *cached)


def chunks_of_16(module, *cached):
    """A scan's chunks at most 16 rows (``ROWS``), so that a trunk of 48 or 64
    tokens crosses several chunk edges."""
    return patched("chunks_of_16", module, "ROWS", 16, *cached)


# ---------------------------------------------------------------------------
# the cell's rehearsal
# ---------------------------------------------------------------------------

def rehearse(cell, seed, seconds=3, xla_flags=True, timeout=900):
    """``benchmark/run.py --rehearse`` of ``cell`` in a process of its own ->
    ``(its last line, parsed; the finished process)``. ``xla_flags`` False: without
    this suite's eight virtual devices."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if not xla_flags:
        env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--rehearse", "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", "1"],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done


def checked(done):
    """The verdict the rehearsal's ``correct`` check printed."""
    said = next(ln for ln in done.stdout.splitlines() if ln.startswith("[bench] correct check"))
    return json.loads(said[said.index("{"):])


def streamed(cfg, frames=4, batch=2):
    """``frames`` small detector frames through ``InfeedPipeline`` and the
    served ``decoder.frame_step`` of ``cfg`` (bf16 weights), ``batch`` a step,
    its statistics folded into the pipeline's counters -> ``(what each step
    returned, the counters' snapshot, the registry's Prometheus text)``."""
    from benchmark import harness
    from psana_ray_tpu.infeed import InfeedPipeline
    from psana_ray_tpu.obs.registry import MetricsRegistry
    from psana_ray_tpu.records import EndOfStream, FrameRecord
    from psana_ray_tpu.transport import RingBuffer

    params = decoder.init_params(cfg, jax.random.key(1), jnp.bfloat16)
    detector = {"panels": 2, "height": 16, "width": 112, "pedestal_adu": 100.0,
                "photon_adu": 35.0, "bad_pixel_fraction": 0.003}
    calib = harness.make_calibration(detector, 1)
    ids = jnp.arange(PROMPT, dtype=jnp.int32)
    step = jax.jit(lambda f: decoder.frame_step(params, calib, f, ids, cfg=cfg, threshold=10.0))
    rng = np.random.default_rng(2)
    q = RingBuffer(maxsize=8)
    for i in range(frames):
        q.put(FrameRecord(0, i, rng.integers(90, 140, (2, 16, 112)).astype(np.uint16), 9.0))
    q.put(EndOfStream(total_events=frames))
    pipe = InfeedPipeline(q, batch_size=batch, poll_interval_s=0.001)
    outs = []

    def on_result(out, _):
        outs.append(out)
        decoder.fold_step_stats(pipe.metrics, out[1])

    assert pipe.run(lambda b: step(b.frames), on_result=on_result) == frames
    assert all(out[0].shape == (batch, 256) and np.isfinite(np.asarray(out[0])).all() for out in outs)
    registry = MetricsRegistry()
    registry.register("reader", pipe.metrics)
    return outs, pipe.metrics.snapshot(), registry.render_prometheus()


# ---------------------------------------------------------------------------
# the artifacts, once a key
# ---------------------------------------------------------------------------

def frozen(value):
    """``value`` as a hashable that does not depend on a dict's order."""
    if isinstance(value, dict):
        return tuple(sorted((k, frozen(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(frozen(v) for v in value)
    return value


class Kit:
    """One file's ``mapping`` (keywords -> the model's keys at a small size),
    its reference module ``ref`` and its ``tiles`` (the fields ``small`` sets
    on the configuration); ``loud`` where the file's weights are loud in a way
    of their own, ``trunk_of`` / ``reference_of`` where the file calls the
    program or the reference another way than the defaults below,
    ``patches`` (name -> a function returning a context manager) for what its
    artifacts may be computed under beside :data:`HIGHEST`, and ``frame``, the
    patches a sequence has where they are not 56. With no model (``Kit()``):
    :meth:`made` alone, a cache by key."""

    def __init__(self, mapping=None, ref=None, tiles=None, loud=loud, trunk_of=None, reference_of=None,
                 patches=None, frame=PATCHES):
        self.mapping, self.ref, self.tiles, self.loud = mapping, ref, tiles or {}, loud
        self.inputs = functools.partial(inputs, patches=frame)
        self.patches = patches or {}
        if trunk_of is not None:
            self.trunk_of = trunk_of
        if reference_of is not None:
            self.reference_of = reference_of
        self._made = {}

    def small(self, m, **tiles):
        """``m``'s configuration with tiles that cut the sequence into several."""
        return dataclasses.replace(decoder.DecoderConfig.from_mapping(m), **{**self.tiles, **tiles})

    # -- plain computations, cached by nobody --------------------------------

    @staticmethod
    def trunk_of(params, patches, ids, cfg, pos=None, jit=True, **kw):
        """The program's trunk and logits at every position of the batch, then
        whatever else ``decoder.trunk`` returned (the statistics; under
        ``exits`` the exit distribution). ``jit`` False: operation by operation,
        which on the CPU is the cheaper way to ONE run of a small trunk."""
        batch, s = patches.shape[0], patches.shape[1] + ids.shape[0]

        def run(p):
            return decoder.trunk(p, embedded(p, patches, ids, cfg), np.arange(s) if pos is None else pos,
                                 cfg, batch, **kw)

        x, *rest = (jax.jit(run) if jit else run)(params)
        return (x, decoder.logits_of(decoder.head_params(params), x, cfg), *rest)

    def reference_of(self, params, patches, ids, sizes):
        x = jnp.concatenate([self.ref.hidden(params, frame, ids, sizes, block=16) for frame in patches])
        return x, self.ref.logits_of(params, x, sizes)

    # -- the cache -------------------------------------------------------------

    @contextlib.contextmanager
    def under(self, names):
        """The patches ``names`` and no other: the matmul precision is SET
        (``highest`` or the default), whatever the caller holds; a module patch
        the caller holds and the key does not name is refused."""
        held = set(ACTIVE) - set(names)
        assert not held, f"asked for an artifact under {sorted(names)} while {sorted(held)} is patched in"
        with contextlib.ExitStack() as stack:
            stack.enter_context(jax.default_matmul_precision(HIGHEST if HIGHEST in names else None))
            for name in names:
                if name != HIGHEST:
                    stack.enter_context(self.patches[name]())
            yield

    def made(self, kind, key, make, under):
        """``make()``, once for ``(kind, key, under)``; ``under`` None where no
        patch can reach the result (a draw of weights). -> a fresh tree around
        the cached arrays."""
        key = (kind, frozen(key), None if under is None else tuple(sorted(under)))
        if key not in self._made:
            with contextlib.nullcontext() if under is None else self.under(under):
                self._made[key] = make()
        return jax.tree.map(lambda a: a, self._made[key])

    def params(self, seed, over=None):
        """Loud float32 weights of ``mapping(**over)`` drawn from ``seed``."""
        over = over or {}

        def make():
            cfg = decoder.DecoderConfig.from_mapping(self.mapping(**over))
            return self.loud(decoder.init_params(cfg, jax.random.key(seed), jnp.float32))

        return self.made("params", (seed, over), make, None)

    def trunk(self, seed, batch=1, over=None, tiles=None, under=(HIGHEST,), **kw):
        """``trunk_of`` on the seed's weights and inputs; ``kw`` goes to it."""
        over, tiles = over or {}, tiles or {}

        def make():
            cfg = self.small(self.mapping(**over), **tiles)
            return self.trunk_of(self.params(seed, over), *self.inputs(seed, batch), cfg, **kw)

        return self.made("trunk", (seed, batch, over, tiles, kw), make, under)

    def reference(self, seed, batch=1, over=None, under=(HIGHEST,), **faults):
        """``reference_of`` on the same, under ``ref.sizes(m, **faults)``: the
        clean reference where ``faults`` is empty."""
        over = over or {}

        def make():
            sizes = self.ref.sizes(self.mapping(**over), **faults)
            return self.reference_of(self.params(seed, over), *self.inputs(seed, batch), sizes)

        return self.made("reference", (seed, batch, over, faults), make, under)
