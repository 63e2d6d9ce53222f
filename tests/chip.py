"""What the three ``tests/test_chip_compile*.py`` share (moved out of the one
file they were): the epix10k2M shapes, a program compiled for the DESCRIBED
v5e under ``one_chip``'s settings (``tests/conftest.py``), a decoder cell's
configuration and its served step lowered at the sizes the benchmark runs, and
``compiled``: a case's compiled TEXT and memory numbers, made once a worker."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PANELS, H, W = 16, 352, 384  # epix10k2M
BF16, F32 = jnp.bfloat16, jnp.float32
S = jax.ShapeDtypeStruct  # case arguments are shapes; the test adds the device
SHAPE = re.compile(r"\b(f32|s32|bf16|u16|pred|u8|s8)\[([\d,]*)\]")


def compile_case(fn, arg_shapes, one_chip, monkeypatch):
    """One of ``CASES``' programs compiled for the described chip."""
    # code that asks default_backend() would take its CPU (interpret)
    # branch under a described topology; steer it here, not in the program
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip), arg_shapes)
    return jax.jit(fn).lower(*args).compile()  # raises what the chip's compiler would


_COMPILED = {}  # a case's builder -> (text, memory, what else the builder returned)


def compiled(case, one_chip, monkeypatch):
    """``case() -> (fn, argument shapes, ...)`` compiled for the described chip
    ONCE a worker, whichever test asks first (under its ``one_chip``: cache
    off, full tracebacks, ``default_backend`` steered) -> ``(the compiled
    text, (argument, output, temporary bytes), the rest of what the builder
    returned)``: text and numbers, no live executable and no trace."""
    if case not in _COMPILED:
        fn, arg_shapes, *rest = case()
        done = compile_case(fn, arg_shapes, one_chip, monkeypatch)
        mem = done.memory_analysis()
        _COMPILED[case] = (done.as_text(), (
            mem.argument_size_in_bytes, mem.output_size_in_bytes, mem.temp_size_in_bytes), rest)
    return _COMPILED[case]


def decoder_cell(name):
    """A decoder cell's configuration as the benchmark runs it: the mapping, the
    ``DecoderConfig`` and the parameters' shapes."""
    import json

    from psana_ray_tpu.models import decoder

    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    dcfg = decoder.DecoderConfig.from_mapping(cfg)
    return cfg, dcfg, jax.eval_shape(lambda k: decoder.init_params(dcfg, k), jax.random.key(0))


def lowered_step(name, one_chip):
    """A decoder cell's served step, lowered for the described chip at the
    sizes the benchmark runs: ``(the mapping, the DecoderConfig, the lowering)``."""
    from psana_ray_tpu.models import decoder

    cfg, dcfg, params = decoder_cell(name)
    calib = (S((PANELS, H, W), F32), S((PANELS, H, W), F32), S((PANELS, H, W), jnp.uint8))
    frames = S((cfg["batch_size"], PANELS, H, W), jnp.uint16)
    ids = S((cfg["prompt_tokens"],), jnp.int32)

    def step(p, c, f, i):
        return decoder.frame_step(p, c, f, i, cfg=dcfg, threshold=10.0)

    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip), (params, calib, frames, ids))
    return cfg, dcfg, jax.jit(step).lower(*args)


def array_sized_moves(entry, floor, opcodes, apart=None):
    """``name type[dims]`` of every instruction of a compiled entry
    computation that only MOVES an array of ``floor`` elements or more: one
    of ``opcodes``, or a copy/bitcast fusion (a ``convolution_bitcast_fusion``
    is a PRODUCT that writes its result in its reader's layout: no move);
    lines that carry ``apart`` (a scope of its own account) left out."""
    moved = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(", line)
        if not m or (apart and apart in line):
            continue
        op_name, dtype, dims, opcode = m.groups()
        moves = opcode in opcodes or (
            opcode == "fusion" and ("copy" in op_name or "bitcast" in op_name)
            and "convolution" not in op_name)
        if moves and np.prod([int(x) for x in dims.split(",") if x]) >= floor:
            moved.append(f"{op_name} {dtype}[{dims}]")
    return moved


