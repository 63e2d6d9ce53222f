"""Ask the chip's compiler before the chip: ONE layer, or one kernel as a
step calls it, at the published sizes for a DESCRIBED v5e:2x2 topology (as
``tests/test_chip_compile.py``): where a layer's operands reach its kernel,
what a kernel's scratch holds, what a single call compiles to.

Also here: the compile-cache placement rule, the smoke's refusal to pass
off the chip, and the producer staying JAX-free (the one-process-per-chip
rule's cheap guards).
"""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip import BF16, F32, H, PANELS, REPO, S, W, array_sized_moves, decoder_cell
from test_chip_compile_kernels import KEYE_S, _calib


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones among them."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for inner in eqn.params.values():
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(getattr(inner, "jaxpr", inner), "eqns"):
                yield from _pallas_calls(getattr(inner, "jaxpr", inner))


def test_the_delta_net_kernel_the_chip_compiles_carries_its_state_float32():
    """The file states a float32 state a head, and on the chip no limit of the
    cell's ``correct`` tells a state CARRIED in bf16 from it (0.36-2.23
    yardsticks of 4: the state is a bf16 MXU operand either way). So the
    kernel Mosaic is handed, traced at the published sizes as the step calls
    it (not interpreted), is read: its one scratch is ``float32 [6, 192,
    128]`` (six heads a grid step, the state transposed, a head's 96 keys at
    128 lanes), what is stored there is float32, and nothing of a state's
    shape is ever widened from bf16 (rounded on its way to the next chunk)."""
    import functools

    from psana_ray_tpu.ops import delta_rule as dr

    _, dcfg, _ = decoder_cell("olmo_hybrid_7b_prefill_epix10k2m")
    t, h, d_v, bf16 = 8704, dcfg.num_heads, dcfg.linear_value_dim, jnp.bfloat16
    wide = jax.eval_shape(lambda u: dr.lanes_a_head(u, h), S((1, h * dcfg.linear_head_dim), bf16)).shape[1]
    operands = (S((t, wide), bf16), S((t, wide), bf16), S((t, h * d_v), bf16), S((t, h), F32),
                S((t, h * d_v), bf16), S((t, h), F32), S((h,), F32), S((h,), F32), S((d_v,), bf16))
    traced = jax.make_jaxpr(functools.partial(
        dr.gated_delta_net, seq_len=t, heads=h, key_dim=dcfg.linear_head_dim, eps=dcfg.rms_eps,
        chunk=dcfg.linear_chunk, interpret=False))(*operands)

    (call,) = _pallas_calls(traced.jaxpr)
    assert call.params["name"] == "gated_delta_net" and not call.params["interpret"]
    (scratch,) = call.params["grid_mapping"].scratch_avals
    group, lanes = dr.head_group(h, wide // h, d_v), wide // h
    assert (group, lanes) == (dr.HEAD_GROUP, 128) == (6, 128)
    assert scratch.dtype == F32 and scratch.shape == (group, d_v, lanes)
    body = call.params["jaxpr"]
    state_ref = body.invars[-1]
    assert state_ref.aval.shape == scratch.shape and state_ref.aval.dtype == F32
    stored = [eqn.invars[1].aval for eqn in body.eqns
              if eqn.primitive.name == "swap" and eqn.invars[0] is state_ref]
    assert len(stored) == group and all(a.dtype == F32 and a.shape == (d_v, lanes) for a in stored)
    rounded = [eqn for eqn in body.eqns if eqn.primitive.name == "convert_element_type"
               and eqn.invars[0].aval.dtype == bf16 and eqn.invars[0].aval.shape == (d_v, lanes)]
    assert not rounded


def test_the_lightning_kernel_the_chip_compiles_carries_its_state_float32(one_chip, monkeypatch):
    """The file states a float32 state a head; a state CARRIED in bf16 would
    pass the chip's limits (it is a bf16 MXU operand either way: olmo_hybrid's
    finding). So the kernel Mosaic is handed, traced at the published sizes as
    the step calls it (not interpreted), is read: its first scratch is
    ``float32 [4, 128, 128]`` (four heads a grid step), what is stored there is
    float32 of a state's shape, and the kernel alone compiles for the described
    v5e at S 34,304, 32 heads of 128 x 128, in chunks of 256 rows."""
    import functools

    from psana_ray_tpu.ops import lightning

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, h, d = 34304, 32, 128
    operands = (S((t, h * d), F32), S((t, h * d), F32), S((t, h * d), BF16), S((t, h * d), BF16),
                S((h,), F32), S((d,), BF16), S((d,), BF16), S((d,), BF16), (S((t, d), F32), S((t, d), F32)))
    fn = functools.partial(lightning.lightning_attention, seq_len=t, heads=h, eps=1e-6,
                           scale=d ** -0.5, interpret=False)
    traced = jax.make_jaxpr(fn)(*operands)

    (call,) = _pallas_calls(traced.jaxpr)
    assert call.params["name"] == "lightning_attention" and not call.params["interpret"]
    state, masks, falls, left = call.params["grid_mapping"].scratch_avals
    assert state.dtype == F32 and state.shape == (lightning.HEADS, d, d) == (4, 128, 128)
    assert masks.shape == (4, 256, 256) and lightning.step_rows(t) == (512, 256)
    body = call.params["jaxpr"]

    def swaps(jaxpr):  # every store into a ref of the state's shape, the chunk loop's body included
        for eqn in jaxpr.eqns:
            ref = eqn.invars[0].aval if eqn.invars else None
            if eqn.primitive.name == "swap" and getattr(ref, "shape", None) == state.shape:
                assert ref.dtype == F32
                yield eqn.invars[1].aval
            for inner in eqn.params.values():
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(getattr(inner, "jaxpr", inner), "eqns"):
                    yield from swaps(getattr(inner, "jaxpr", inner))

    stored = list(swaps(body))
    assert all(a.dtype == F32 for a in stored)  # a head's state a chunk leaves, four a chunk
    assert [a.shape for a in stored if a.shape == (d, d)] == [(d, d)] * lightning.HEADS
    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip), operands)
    assert jax.jit(fn).lower(*args).compile().as_text().count("tpu_custom_call") >= 1


def test_the_selective_scan_the_chip_compiles_holds_one_float32_state_and_no_token_channel_state_array(
        one_chip, monkeypatch):
    """Mamba-1's scan at the published sizes (2 x 8,704 tokens, 5,120 channels
    over a state of 16), traced as the step calls it (not interpreted): ONE
    kernel whose scratch holds ONE float32 array of a state's size, ``[10,
    16, 512]`` (a channel tile a slot); nothing the call makes outside or
    inside it is as large as ``[T, 5,120, 16]`` (5.7 GB in float32: what an
    associative scan of XLA's would write) or loops over the tokens in HBM;
    and Mosaic takes it for the described v5e, with the second output a
    later layer's memory unit reads."""
    import functools

    from psana_ray_tpu.ops import selective_scan as ss

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, s, c, n = 17408, 8704, 5120, 16
    operands = (S((t, c), BF16), S((t, c), F32), S((t, ss.LANES), BF16), S((t, c), BF16),
                S((c, n), F32), S((c,), F32), S((c,), F32))
    fn = functools.partial(ss.selective_scan, seq_len=s, keep=True, interpret=False)
    traced = jax.make_jaxpr(fn)(*operands)
    (call,) = _pallas_calls(traced.jaxpr)
    assert call.params["name"] == "selective_scan" and not call.params["interpret"]
    assert ss.scan_tiles(s, c) == (256, 512)
    scratch = call.params["grid_mapping"].scratch_avals
    states = [a for a in scratch if int(np.prod(a.shape)) == c * n]
    assert [(a.shape, a.dtype) for a in states] == [((10, n, 512), F32)]
    assert all(a.dtype == F32 for a in scratch)
    made = [v.aval for eqn in traced.jaxpr.eqns for v in eqn.outvars]
    assert max(int(np.prod(a.shape)) for a in made + list(scratch)) <= t * c < t * c * n
    assert not any(eqn.primitive.name in ("scan", "while") for eqn in traced.jaxpr.eqns)
    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip), operands)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * t * c  # no array beside its operands


def test_one_differential_windowed_layer_compiles_at_the_published_sizes_as_two_band_calls(
        one_chip, monkeypatch):
    """Phi-4-mini-flash's windowed layer at 2 x 8,704 tokens: 20 head pairs
    over 10 key pairs of 2 x 64, two calls of the batched kernel at ``d`` 64,
    ``dv`` 128, two query half-heads a key half-head, a query tile of 256 a
    grid step against ONE key window of 768 rows that follows the diagonal
    (PR 77; head-major keys of 64, Element-addressed) under the window of 512
    — shapes no other cell compiles — and nothing else of Mosaic's in the
    layer."""
    import json

    from psana_ray_tpu.models import decoder
    from psana_ray_tpu.parallel import sparse_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(REPO, "benchmark", "configs", "phi4_mini_flash_prefill_epix10k2m.json")) as f:
        cfg = decoder.DecoderConfig.from_mapping(json.load(f))
    assert cfg.layer_types[1] == decoder.SLIDING and cfg.sliding_window == 512
    assert sa.causal_tiles(8704, 2, cfg.causal_q_tile, cfg.causal_kv_tile, 512) == (256, 768)
    shapes = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.key(0))["layers"][1]
    layer = {k: v for k, v in shapes.items() if not k.startswith(("w_gate", "w_up", "w_down", "norm2"))}
    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip),
                        (layer, S((17408, 2560), BF16)))
    text = jax.jit(lambda p, x: decoder.diff_attention(p, x, 2, cfg, 1, window=512)[0]).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") == 2 and text.count("windowed_gqa_attention") >= 2


def test_the_block_selection_and_the_call_under_its_flags_compile_at_sixteen_heads_a_group(
        one_chip, monkeypatch):
    """The sparse layer's two calls ALONE at the published sizes (S 34,304, 32
    query heads on 2 key heads of 128): the selection kernel (a query tile's
    ``[128, 2560]`` score row a head, four lane segments of 640 blocks) and the
    masked causal kernel under its flags, ``causal_tiles``, ``mask_tile`` and
    ``heads_a_step`` at a shape keye's eight and nemotron3's maskless sixteen
    have not compiled."""
    import functools

    from psana_ray_tpu.parallel import sparse_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, h, g, d = 34304, 32, 2, 128
    sel = sa.BlockSelection()
    q, k = S((t, h * d), BF16, sharding=one_chip), S((t, g * d), BF16, sharding=one_chip)
    select = jax.jit(functools.partial(sa.select_blocks, num_kv_heads=g, selection=sel, interpret=False))
    text = select.lower(q, k).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "s8[2,34304,640]" in text
    flags = S((g, t, 640), jnp.int8, sharding=one_chip)
    q3, k3 = (S((1, *a.shape), BF16, sharding=one_chip) for a in (q, k))
    attend = jax.jit(lambda q, k, v, m: sa.masked_gqa_attention(
        q, k, v, m, num_kv_heads=g, block_q=256, mask_blocks=sel, interpret=False))
    compiled = attend.lower(q3, k3, k3, flags).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert sa.heads_a_step(g, h // g, 128, 2048, d, d, masked=True) == 1


@pytest.mark.parametrize("name", ["kimi_k2_prefill_epix10k2m", "deepseek_v32_prefill_epix10k2m"])
def test_latent_attention_s_operands_reach_the_kernel_where_their_products_wrote_them(
        name, one_chip, monkeypatch):
    """ONE latent layer (``decoder.latent_attention``) at the cell's
    published widths, batch and 8,704 tokens a sequence, as compiled: in
    the entry computation no ``copy``, ``slice``, ``reshape`` or
    copy/bitcast fusion writes an array of ``T * H * 64`` elements or more
    between the projections' products, ``masked_gqa_attention`` and ``W_o``
    (the indexer's own head-major index queries apart: its scope). On PR
    47's tree this counted ten in kimi's layer, beside a pass that scaled
    and converted the float32 query: the 128-wide query sliced out of a
    float32 ``[T, H*192]`` product, relaid, and transposed head-major
    (three); the rotary query reshaped and copied (two); keys and values
    each relaid and transposed (four); the output transposed back (one);
    and six in dsv32's (the query's slice and relayout, the keys-and-values
    product relaid whole and then a copy each, the output's): 3.4 GB
    written a layer that computed nothing. Since PR 48 the kernel reads q,
    k, v and writes o as column blocks of the products' own token-major
    arrays (k and v of ONE array). Since PR 61 the kernel turns the 64-wide
    rotary query itself, a query tile at a time: ``W_uq``'s rotary product
    writes it float32, unturned, head-major ``[H, 1, T, 64]`` (the kernel's
    operand: ONE array of ``T * H * 64`` elements, where PR 48's tree wrote
    three between that product and the kernel: the float32 product 570 MB,
    the rotary's two float32 halves ``[T, H, 32]`` 1,140 MB in lanes a
    quarter full, the scaled bf16 head-major copy 285 MB), and no float32
    ``[T, H, 32]`` array exists."""
    from psana_ray_tpu.models import decoder

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, params = decoder_cell(name)
    batch, seq = cfg["batch_size"], 8704
    tokens, heads = batch * seq, dcfg.num_heads

    def layer(p, x):
        angles = decoder.rotary_angles(np.arange(seq), dcfg.rope_theta, dcfg.rope_dim // 2,
                                       yarn=dcfg.rope_yarn)
        return decoder.latent_attention(p, x, jnp.tile(angles, (batch, 1)), batch, dcfg, angles)

    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip),
                        (params["layers"][1], S((tokens, dcfg.hidden_size), BF16)))
    text = jax.jit(layer).lower(*args).compile().as_text()
    entry = text[text.index("ENTRY"):]
    assert len(re.findall(r"^\s*(?:ROOT )?%masked_gqa_attention[.\d]* = ", entry, re.M)) == 1
    assert f"[{tokens},{heads * dcfg.head_dim}]" not in entry  # no product of whole [nope | rope] heads
    moved = array_sized_moves(entry, tokens * heads * 64, ("copy", "slice", "reshape"), "/indexer/")
    assert not moved, moved
    # the rotary query: no half of it is ever an array, and what the kernel reads is what the
    # product wrote (the indexer's index queries have as many elements in dsv32: its scope apart)
    dr = dcfg.qk_rope_head_dim
    assert f"f32[{tokens},{heads},{dr // 2}]" not in entry and "multiply_subtract_fusion" not in entry
    rotary = [f"{m.group(1)} {dtype}[{dims}]" for line in entry.splitlines() if "/indexer/" not in line
              for m in [re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) [\w\-]+\(", line)] if m
              for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", m.group(2))
              if np.prod([int(x) for x in dims.split(",")]) == tokens * heads * dr]
    assert rotary == [f"%convolution_bitcast_fusion f32[{heads},1,{tokens},{dr}]"], rotary


@pytest.mark.parametrize("name,kind", [
    ("laguna_s21_prefill_epix10k2m", "sliding_attention"),
    ("laguna_s21_prefill_epix10k2m", "full_attention"),
    ("ouro_2p6b_prefill_epix10k2m", "full_attention")], ids=["laguna-sliding", "laguna-full", "ouro"])
def test_laguna_s_grouped_heads_reach_the_kernel_where_their_products_wrote_them(
        name, kind, one_chip, monkeypatch):
    """ONE attention layer (``decoder._attention``) as compiled: laguna's
    windowed one at 72 query heads and its full one at 48 (8 key heads of
    128, two sequences of 8,704, the output gated), and ONE layer
    application of the looped reader's (16 heads on 16 key heads, two
    sequences of 2,304, ``[4608, 2048]``): one kernel, its output the
    token-major ``[B, 1, S, H*128]`` that ``W_o`` reads, and between
    ``W_q``'s and ``W_k``'s products and ``W_o`` NOTHING of ``T * H * 64``
    elements or more that only moves: no ``copy``, ``transpose``,
    ``reshape``, ``convert``, ``broadcast`` or copy/bitcast fusion. On PR
    57's tree a windowed layer held q's head-major copy (bf16
    ``[2,8,9,8704,128]``) and THREE float32 passes over o on the way back
    with the gate broadcast to ``[T, H, 128]`` beside them; PR 58 left the
    rotary's own: the two 64-lane halves of every head sliced out of the
    float32 product into ``f32[T, H, 64]`` copies (fifteen a step in
    laguna's, 21-26 ms; 768 in the looped reader's, 49.7 ms) and turned in
    lane-padded passes. Since PR 63 the kernel's q and k ARE the products'
    results, float32 and unturned, through bitcasts alone, and the kernel
    turns them by the step's two tables (``jit(turn_tables)``, ``[T,
    128]`` float32 each): no float32 half of a head exists. (A layer ALONE
    copies its input and its result, the entry computation's parameter and
    root, into the layout its neighbours would have given them: those two
    of the looped reader's are not between the products and ``W_o``.)"""
    from psana_ray_tpu.models import decoder

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, params = decoder_cell(name)
    batch, seq, i = cfg["batch_size"], cfg["sequence_tokens"], cfg["layer_types"].index(kind)
    tokens, heads = batch * seq, dcfg.heads(i)
    sliding = kind == "sliding_attention"
    assert (heads, tokens) == {"laguna-sliding": (72, 17408), "laguna-full": (48, 17408),
                               "ouro-full": (16, 4608)}[name.split("_")[0] + "-" + kind.split("_")[0]]

    def layer(p, x):
        if sliding:
            angles = decoder.rotary_angles(np.arange(seq), dcfg.sliding_rope_theta, dcfg.head_dim // 2)
        else:
            angles = decoder.rotary_angles(np.arange(seq), dcfg.rope_theta, dcfg.rope_dim // 2,
                                           yarn=dcfg.rope_yarn)
        return decoder._attention(p, x, jnp.tile(angles, (batch, 1)), None, batch, dcfg,
                                  dcfg.sliding_window if sliding else 0)[0]

    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip),
                        (params["layers"][i], S((tokens, dcfg.hidden_size), BF16)))
    text = jax.jit(layer).lower(*args).compile().as_text()
    entry = text[text.index("ENTRY"):]
    made = {m.group(1): (m.group(2), line) for line in entry.splitlines()
            for m in [re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \S+ ([\w\-]+)\(", line)] if m}
    kernel = "windowed_gqa_attention" if sliding else "masked_gqa_attention"
    calls = [line for made_by, (_, line) in made.items() if made_by.startswith("%" + kernel)]
    assert len(calls) == 1 and f" = bf16[{batch},1,{seq},{heads * dcfg.head_dim}]" in calls[0]
    moved = array_sized_moves(entry, tokens * heads * 64,
                               ("copy", "transpose", "reshape", "convert", "broadcast"))

    def at_the_edge(move):  # the entry's root, or a copy of one of its parameters
        line = made[move.split()[0]][1]
        source = re.search(r"copy\((%[\w.\-]+)\)", line)
        return line.lstrip().startswith("ROOT") or bool(source) and "parameter(" in made[source.group(1)][1]

    assert [m for m in moved if not at_the_edge(m)] == [], moved
    assert len(moved) == (2 if name.startswith("ouro") else 0), moved
    # the kernel's q and k: each, through bitcasts alone, a product's own float32 result
    operands = re.findall(r"%[\w.\-]+", calls[0].split("custom-call(")[1].split(")")[0])
    for operand, columns in ((operands[2], heads), (operands[3], dcfg.num_kv_heads)):
        assert f" = f32[" in made[operand][1], made[operand][1][:200]
        while made[operand][0] == "bitcast":
            operand = re.search(r"bitcast\((%[\w.\-]+)\)", made[operand][1]).group(1)
        kind_of, line = made[operand]
        assert kind_of == "fusion" and "/dot_general" in line and (
            f"f32[{tokens},{columns * dcfg.head_dim}]" in line), line[:300]
    assert f"f32[{tokens},{heads},{dcfg.head_dim // 2}]" not in entry and "multiply_subtract_fusion" not in entry
    assert sum("jit(turn_tables)" in line and f"f32[{tokens},{dcfg.head_dim}]" in line.split(" fusion(")[0]
               for _, line in made.values()) == 2  # [cos | cos | 1], [-sin | sin | 0]


@pytest.mark.parametrize("kind,heads,window,parts,products", [
    ("full", 48, None, 3, 2 * 3 * 2), ("windowed", 72, 512, 3, 1 * 3 * 2)])
def test_laguna_s_stacked_calls_compile_with_their_rows_in_parts(kind, heads, window, parts, products,
                                                                   one_chip, monkeypatch):
    """Laguna's two calls ALONE at the published sizes (2 x 8,704 tokens, 8
    key heads of 128, q and k float32 for the kernel to turn, the gate a
    head), as the step makes them since PR 75: a grid step's stacked group
    cut into ``parts`` runs of whole heads (``parts_a_step``: three parts of
    TWO heads at the full layers' 512 x 1,088; since PR 77 three parts of
    THREE at the windowed ones' 256 rows against ONE key window of 768, whose
    body is ONE branch where the band's tiles held four), the body's
    products two a part and branch (two branches: below the diagonal and on
    it; under the window one, the whole key window's compare), the
    scratch what it was (``m``, ``l``, ``acc`` and the turned query tile,
    stacked: a part is a slice of each; one softmax pass writes no ``m``),
    and Mosaic takes the written order within ``_VMEM_LIMIT``. Compile
    seconds for the described v5e here, the parent's one stacked product ->
    three parts, lowering included (PR 75): full 8.8 -> 6.3; on the chip,
    first call, 6.5 -> 7.0 (the windowed call at three parts 4.2 -> 5.8)."""
    from psana_ray_tpu.parallel import sparse_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, s, g, d = 2, 8704, 8, 128
    bq, bk = sa.causal_tiles(s, heads // g, 1088, 1088, window, d)
    assert (bq, bk) == ((256, 768) if window else (512, 1088))
    assert sa.parts_a_step(heads // g, bq, bk, window=window) == parts

    def fn(q, k, v, cos, sin, gate):
        attend = sa.windowed_gqa_attention if window else sa.masked_gqa_attention
        return attend(q, k, v, num_kv_heads=g, block_q=1088, block_k=1088, interpret=False,
                      out_gate=gate, turn=(cos, sin), turn_width=d if window else d // 2,
                      q_scale=d ** -0.5, **({"window": window} if window else {}))

    table = S((b * s, d), F32)
    operands = (S((b, s, heads * d), F32), S((b, s, g * d), F32), S((b, s, g * d), BF16), table, table,
                S((b, s, heads), F32))
    (call,) = _pallas_calls(jax.make_jaxpr(fn)(*operands).jaxpr)

    def count(jaxpr, name):  # through the branches' conds
        return sum((eqn.primitive.name == name) + sum(
            count(getattr(inner, "jaxpr", inner), name) for value in eqn.params.values()
            for inner in (value if isinstance(value, (tuple, list)) else (value,))
            if hasattr(getattr(inner, "jaxpr", inner), "eqns")) for eqn in jaxpr.eqns)

    assert count(call.params["jaxpr"], "dot_general") == products
    rows = heads // g * bq
    assert [a.shape for a in call.params["grid_mapping"].scratch_avals] == [
        (rows, 1), (rows, 1), (rows, d), (rows, d)]
    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip), operands)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert ("windowed_gqa_attention" if window else "masked_gqa_attention") in text


def test_keye_s_selection_attention_operands_reach_the_kernel_with_two_pads_and_no_new_copy(
        one_chip, monkeypatch):
    """ONE attention layer of keye's (``decoder._attention``: 32 heads on 4
    key heads of 128, the indexer's selection, one sequence of 34,304) as
    compiled since PR 68 (``-k reach_the_kernel``'s count for this layer):
    ONE ``masked_gqa_attention`` call on ``[1, S, .]`` operands under the
    mask ``[268, 16, 128, 2176]``, its output the token-major ``[1, 1, S,
    4096]`` that ``W_o``'s product reads as it is. Between ``W_q``'s product
    and ``W_o`` what only moves ``S * 512`` elements or more is the PARENT's
    two relayouts — XLA turns q and k with the tokens in the lanes
    (``{0,2,1}``) and copies each row-major for the kernel, 281 + 35 MB a
    layer (ROADMAP S13: ``_kernel_turns`` refuses a selection) — and this
    PR's two pads of k and v to the mask's sixteen whole key tiles (35.6 MB
    each). No head-major copy of q (the group's token-major block is stacked
    in the kernel), nothing of o."""
    from psana_ray_tpu.models import decoder

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, params = decoder_cell("keye_vl2_prefill_epix10k2m")
    seq, heads, patch = KEYE_S, dcfg.num_heads, cfg["patch"]
    pos = decoder.frame_positions(PANELS, H // patch, W // patch, cfg["prompt_tokens"])
    assert len(pos) == seq and cfg["batch_size"] == 1

    def layer(p, x):
        angles = decoder.rotary_angles(pos, dcfg.rope_theta, dcfg.rope_dim // 2, dcfg.mrope_section)
        idx = decoder.rotary_angles(np.arange(seq), dcfg.rope_theta, dcfg.indexer_head_dim // 2)
        return decoder._attention(p, x, angles, idx, 1, dcfg)[0]

    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip),
                        (params["layers"][1], S((seq, dcfg.hidden_size), BF16)))
    text = jax.jit(layer).lower(*args).compile().as_text()
    entry = text[text.index("ENTRY"):]
    made = {m.group(1): (m.group(2), line) for line in entry.splitlines()
            for m in [re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \S+ ([\w\-]+)\(", line)] if m}
    calls = [line for made_by, (_, line) in made.items() if made_by.startswith("%masked_gqa_attention")]
    assert len(calls) == 1 and f" = bf16[1,1,{seq},{heads * dcfg.head_dim}]" in calls[0]
    assert "s8[268,16,128,2176]" in calls[0]
    kv = dcfg.num_kv_heads * dcfg.head_dim
    moved = array_sized_moves(entry, seq * kv, ("copy", "transpose", "reshape", "convert", "pad"),
                               "/indexer/")  # (its head-major index queries: its scope's account)
    index_halves = f"f32[{seq},{dcfg.indexer_heads},{dcfg.indexer_head_dim // 2}]"  # and their rotary
    moved = sorted(m.split(" ", 1)[1] for m in moved if index_halves not in m)
    assert moved == sorted([f"bf16[1,1,{seq},{heads * dcfg.head_dim}]", f"bf16[1,{seq},{kv}]",
                            f"bf16[1,{16 * 2176},{kv}]", f"bf16[1,{16 * 2176},{kv}]"]), moved
    # W_o's product reads what the kernel wrote
    root = next(line for _, line in made.values() if line.lstrip().startswith("ROOT"))
    assert "/dot_general" in root and calls[0].split(" = ")[0].strip() in root, root[:300]


@pytest.mark.parametrize("d", [0, 1, 2, 3])
@pytest.mark.parametrize("r", [1, 2, 4])
def test_local_maxima_cuts_its_phases_without_a_gather(r, d):
    """``x[ry::b, rx::b]`` traces to the ``gather`` primitive, and on the
    TPU each phase then costs a row fetch a row (PR 41: 1.35 ms of nine
    gathers a step); ``lax.slice`` is the static form. Runs on the CPU."""
    from psana_ray_tpu.models.peaks import _local_maxima

    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from primitives(sub)

    logits = S((3, 48 // r, 64 // r, r * r), F32)
    names = set(primitives(jax.make_jaxpr(lambda x: _local_maxima(x, 0.5, d, d + 1, r))(logits).jaxpr))
    assert "slice" in names and "gather" not in names


# -- the compile cache can be placed from outside ---------------------------

def test_compile_cache_honours_the_environment(cache_setting, monkeypatch, tmp_path):
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; code set no directory over it
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_the_checkout(cache_setting, monkeypatch):
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert configure_compile_cache() == want  # fixed: no pid, time or tmp name
    assert jax.config.jax_compilation_cache_dir == want


def test_kernel_program_is_the_same_from_any_call_stack(one_chip, monkeypatch):
    """A Pallas kernel's serialized module must not carry its callers'
    Python stack, or one step compiled from two entry points gets two
    persistent-cache keys (seen on the v5e: CLI child vs. script)."""
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")  # set no dir here
    configure_compile_cache()
    step, arg_shapes, _ = _calib(jnp.uint16)
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in arg_shapes]

    def through_another_caller(*a):
        return step(*a)

    texts = []
    for fn in (step, through_another_caller):
        jax.clear_caches()  # else the second lowering reuses the first trace
        fn.__name__ = "step"  # the module is named after the function
        texts.append(jax.jit(fn).lower(*args).as_text())
    assert "tpu_custom_call" in texts[0]
    assert texts[0] == texts[1]


# -- chip_smoke.py refuses to pass off the chip -----------------------------

def _run(argv, **env_extra):
    env = dict(os.environ, **env_extra)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        argv, capture_output=True, text=True, timeout=300, env=env, cwd=REPO
    )


def test_chip_smoke_fails_at_the_device_check_on_cpu():
    out = _run([sys.executable, "chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "JAX found no accelerator" in out.stderr  # there, not before


def test_chip_smoke_failed_child_fails_the_run(tmp_path):
    """A phase's child that exits non-zero ends the run non-zero — no
    try/except lets a failed phase reach the result line."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    with pytest.raises(SystemExit) as e:
        chip_smoke.run_child(
            "serve", [sys.executable, "-c", "raise SystemExit(3)"],
            str(tmp_path / "serve.log"),
        )
    assert e.value.code == 1


def test_producer_cli_never_imports_jax():
    """A producer must not be able to hold the chip: the CLI runs to its
    EOS with ``jax`` absent from ``sys.modules``."""
    code = (
        "import sys; from psana_ray_tpu.producer import main; "
        "main(['--detector_name', 'smoke_a', '--num_events', '4', '--calib']); "
        "assert 'jax' not in sys.modules, 'producer imported jax'; print('JAXFREE')"
    )
    out = _run([sys.executable, "-c", code])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "JAXFREE" in out.stdout
