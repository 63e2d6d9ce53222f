"""Ask the chip's compiler before the chip: every decoder cell's WHOLE
served step at the sizes the benchmark runs, for a DESCRIBED v5e:2x2 topology
(no chip attached — jax.experimental.topologies): lowered and held to its
pinned text (``PINNED_STEPS``), and compiled, with its kernels where the
roofline functions and a trace's scopes count them. Interpret-mode tests
cannot see what Mosaic refuses (unaligned slices, VMEM overflow) nor what does
not fit HBM; these do, at no chip time. Nothing runs, so they say nothing
about results or speed — ``chip_smoke.py`` on the chip does that.

The kernels and layers alone: ``tests/test_chip_compile_kernels.py`` (``CASES``)
and ``tests/test_chip_compile_layers.py`` (operand layouts, single layers, the
cheap guards); what the three share: ``tests/chip.py`` and the fixtures
``one_chip`` and ``cache_setting`` in ``tests/conftest.py``.
"""

import re

import jax
import pytest

from chip import decoder_cell, lowered_step


# sha256 of the served step's lowered text (StableHLO), the kernels' serialized bodies cut out
# (they carry file paths and line numbers): what `decoder.frame_step` traces to for the two
# decoders the benchmark had before PR 42 (and, since PR 46, for PR 42's own). Pinned on PR 41's tree first (PR 42's trunk lowered
# to it), then again in PR 42, knowingly: with every share sent to `_held_rows_moe`, the
# all-held path lost what it did for a share (the held mask and its `where` on the gates, the
# grouped product's group offset of 0, the slice of the per-expert counts); the device times
# did not move (PERF.md section 6). A PR that means to change
# one of these programs re-pins it, knowingly: an equal text is an equal key in the compile
# cache, and a decoder cell's warm `setup_s` (bound 0.1) pays seconds for anything new to trace
PINNED_STEPS = {
    # all four re-pinned in PR 51, knowingly: every decoder's router takes its k experts by k dense
    # passes over [T, E], reads their gates by a compare and a row sum and counts each held
    # expert's slots by column sums of a compare (no top_k, take_along_axis or bincount: ids, raw
    # affinities and counts bit for bit what they were; the renormalising sum over k written out
    # in the order the TPU's lane reduce gave it), and a share-holder's products left moe_route.
    # Before that: dsv32's pinned on PR 49's tree in PR 50; kimi's on PR 43's tree in PR 46 and
    # again in PR 48 (token-major operands for the latent kernel)
    # (the three steps with a latent layer — dsv32's, kimi's and ling3's — re-pinned in PR 61,
    # knowingly: `_latent_projections` hands the rotary query on float32 and unturned, as its
    # product wrote it (ONE three-dimensional product `[T, rq] x [rq, H, 64]`: written as a
    # two-dimensional one and reshaped, six of kimi's seven layers compiled to a column-major
    # product and a copy of it), `latent_attention` makes the step's two angle tables, and the
    # latent kernel takes them as two more operands with a fourth scratch; keye's, lfm2's, laguna's,
    # granite's and the looped reader's were hashed before and after and did not move: a call
    # without the tables traces the kernel it traced, `tests/test_decoder_kimi.py` holds its body)
    # (the seven steps with routed experts — these four, ling3's, laguna's and nemotron3's — re-pinned
    # in PR 65, knowingly: an expert layer's up product is `moe.gmm`, a grouped product of the repo's
    # own (megablox's grid, metadata and store mask) whose last contraction step stores
    # `silu(gate) * acc` (`relu(acc)^2` where the experts have no gate) rounded once: it takes the
    # gate's float32 product as one more operand and writes the hidden rows in the activations' type,
    # and the float32 up product, the `logistic`, the two multiplies and the `convert` that stood
    # between the products in the all-held path, the held rows' loop and the pass ahead are gone
    # from the step's own text; granite's and the looped reader's, which run no grouped product,
    # were hashed before and after and did not move)
    # (the three steps with a latent layer — dsv32's, kimi's and ling3's — re-pinned in PR 66,
    # knowingly: a grid step of `_causal_kernel` takes a BLOCK of heads there
    # (`sparse_attention.heads_a_step`: eight under dsv32's mask, two in kimi's and ling3's latent
    # calls), so the call's grid is `(B, G / hb, pairs)`, keys and values of one array ride in as
    # ONE operand where they were two blocks of it, and the step's statistics vector ends in
    # `BLOCK_STATS` (seventeen values, the last places ONE constant of the shapes); keye's, lfm2's,
    # laguna's, granite's, nemotron3's and the looped reader's were hashed before and after and
    # did not move: heads that share their keys, heads of 64, keye's `_attn_kernel` (gone since PR 68) and a kernel that
    # turns q and k itself take one group a step as they did, and at one head a step the kernel's
    # body is the jaxpr it was (`tests/test_decoder_kimi.py -k traces_the_kernel`))
    # (the five steps whose causal calls hold a STACKED group — keye's, lfm2's, laguna's, granite's and
    # nemotron3's — re-pinned in PR 75, knowingly: a grid step cuts the group's rows into parts
    # (`sparse_attention.parts_a_step`: eight under keye's mask, four at lfm2's and granite's heads of
    # 64 and in nemotron3's sixteen a group, three in laguna's full calls), part p + 1's
    # score product written before part p's softmax; the kernel's body rides in the blanked
    # `backend_config`, so what moved in the step's own text is the statistics vector, which ends in
    # `PART_STATS` (twenty values: `attn_part_tiles_total` last, `BLOCK_STATS` at what the calls take,
    # ONE constant of the shapes). dsv32's, kimi's, ling3's, the looped reader's and olmo_hybrid's were
    # hashed before and after and did NOT move: `rep == 1` in every causal call of theirs)
    "deepseek_v32_prefill_epix10k2m": "4459560881f4932af4843903dfaa127c21ecbadaa451ce1890d074e6fb249eac",
    "kimi_k2_prefill_epix10k2m": "aabd919baf99e48f437abf546ab498c3b2c100d1f38b24cf58eff093ba9c462a",
    # (keye's ALONE re-pinned in PR 68, knowingly: its four selection-attention calls leave
    # `_attn_kernel` (deleted: ROADMAP D12) for the batched causal body under the mask — `[1, S, .]`
    # operands, the mask `[268, 16, 128, 2176]` in a key tile that does not divide 34,304
    # (`mask_tile`), k and v padded to 34,816 rows, a table of 1,128 causal pairs as scalar prefetch,
    # q as ONE token-major block a group with a fourth scratch; the nine others were hashed before
    # and after and did not move: at 8,704 tokens every rule gives what it gave, and the maskless
    # cells take none of the changed branches)
    "keye_vl2_prefill_epix10k2m": "874b8b6514802f5e70b9062a37c41ac16b82f4c8b72feb759a57b56574f6084a",
    "lfm2_8b_a1b_prefill_epix10k2m": "b2f6c0b7cc4c217d608bdd9f3f4d7640728b046b9cc6bb7cd07e77c9307728c3",
    # pinned in PR 56, both hashed on PR 55's tree first and NEITHER moved by it: laguna's runs
    # nothing of `ops/delta_rule.py`; ling3's does, and PR 56 rewrote that kernel's body (the heads
    # of a grid step side by side), but a Mosaic kernel's body rides in its call's `backend_config`,
    # which this test blanks (it carries file names and line numbers): the pin holds what is
    # AROUND a kernel (its operands, their shapes and types, its grid's result) and no kernel's
    # body. A kernel's own cache entry follows its body and its file's path
    # (ling3's again in PR 61: its one latent layer, above)
    # (ling3's and nemotron3's re-pinned in PR 70, knowingly: once an expert layer, in the pass ahead of
    # the held rows' loop, a `rows_as_words` call (`x [34816, 2560 | 2688]` -> `u32[34816, 12, 128]`)
    # and a `row_gather` call (the slots' tokens, twice, and that view -> `[104448 | 156672, .]`)
    # stand where a `gather` of `x` stood (a `pad` of nothing before the view, as on the way back): `row_gather.tile_rows` takes bfloat16 rows of any whole
    # number of 128-column chunks where the call moves at least as many rows as `x` holds and `x` is
    # past what XLA's own gather keeps in vector memory; the loop's turn (2,048 rows) keeps its
    # `gather`. The eight others were hashed before and after and did not move: laguna's `x
    # [17408, 3072]` is 102 MiB and stays XLA's by that rule, kimi's and dsv32's turns move fewer
    # rows than `x` holds, lfm2's and keye's 2,048 columns take the kernel operand for operand as
    # they did, granite's, ouro's and olmo_hybrid's gather no rows)
    # (the FOUR steps that run `decoder.conv_silu` — ling3's, granite's, nemotron3's and olmo_hybrid's —
    # re-pinned in PR 73, knowingly: ahead of every delta rule and every scan a `conv_silu_taps` call
    # (`ops/short_conv.py`: the array, the taps transposed to float32 `[taps, C]` and, in granite's
    # and nemotron3's, the bias as float32 `[1, C]` -> the array's shape and type) stands where the
    # `pad`, the four `slice`s and `convert`s, the multiplies, the adds and the `logistic` of XLA's
    # loop fusion stood: six calls in ling3's step over `[34816, 12288]`, 36 in granite's over
    # `[8704, 4352]`, six in nemotron3's over `[34816, 6144]`, 36 in olmo_hybrid's (q's and k's over
    # `[8704, 3840]` with the taps laid a head at whole lane tiles as before, v's over `[8704, 5760]`);
    # the kernel's output equals the fusion's to the bit at all five shapes on the chip (PERF.md
    # section 5). The six others — keye's, lfm2's, kimi's, dsv32's, laguna's and the looped reader's —
    # were hashed before and after and did not move: none calls `conv_silu`, and lfm2's
    # `gated_conv_taps` call is operand for operand what it was (its body, which now reaches the
    # rows before a row through the function `conv_silu_taps` shares, rides in `backend_config`))
    "ling3_flash_prefill_epix10k2m": "f9b85f1ad1bb9f9a6a99f9637b189a7e8cd137e7bc2380ebe3f580e060ab16d0",
    # laguna's re-pinned in PR 58, knowingly: its nine attention calls take k, v and the query
    # tile's gate where their products wrote them, q as `[G, H/G, B*S, d]` (a layout of the
    # rotary's fusion) and write o token-major `[B, 1, S, H*128]`, already gated, for `W_o` to
    # read; the six others were hashed before and after and did not move (heads alone in their
    # groups were in place already, heads of 64 stay head-major)
    # (again in PR 63, knowingly, with the looped reader's: where a layer has a rotary, heads of whole
    # lane blocks and no selection, `_projections` hands q and k on float32 and unturned as W_q's and
    # W_k's products wrote them, `_attention` makes the layer type's two tables `[T, 128]`, and the
    # batched kernel takes them as four more operands (the query tile's rows and the key tile's) with
    # a fourth scratch, q as ONE token-major block `[B, 1, S, H*128]`; the six others were hashed
    # before and after and did not move: the rule is a branch taken in Python, `angles is None`,
    # heads of 64 and a selection on its other side, and the latent cells' path is not touched)
    # (laguna's ALONE re-pinned in PR 77, knowingly: its six windowed calls take ONE grid step a query
    # tile — a table of 34 (query tile, key window's first tile) pairs as scalar prefetch where 66
    # (query tile, key tile) pairs stood, the grid `(2, 8, 34)` — and the step's statistics vector
    # ends in other constants (`attn_grid_steps_total` 6 x 544 where 6 x 1,056 stood of the windowed
    # layers, `attn_part_tiles_total` three a step of theirs); the key window's Element-addressed
    # blocks and the one-pass body ride in the blanked `backend_config`. The nine others were hashed
    # before and after and did NOT move: none makes a windowed call, and the maskless and masked
    # forms trace the body they traced)
    "laguna_s21_prefill_epix10k2m": "97201b147e7661f7a4769482abb8fb66c6e313cbe1a02fe95dbc28db7c3cde04",
    # pinned in PR 57, which brought it: the six above were hashed on PR 56's tree first and none
    # moved, though every one of them now traces `_projections`, `embed`, `logits_of` and `trunk`
    # through the new fields' branches (taken in Python, before anything is traced)
    "granite4_h_micro_prefill_epix10k2m": "c505aaa9ec91d92190243b4a5e593f2cd6dc452cfe299023e9d682c9bab1415c",
    # pinned in PR 60, which brought it: the seven above were hashed on PR 58's tree first and none
    # moved (the looped trunk, the sandwich and the gate are branches taken in Python, before
    # anything is traced; at one pass `trunk` is the code it was, to the letter)
    # (re-pinned in PR 63 with laguna's, above: its 48 call sites take q and k float32 and unturned)
    "ouro_2p6b_prefill_epix10k2m": "b888bb6e4984349512f6b63d1da4b969d42000eac112d1dc1b8e5c3303d092ce",
    # pinned in PR 64, which brought it: the eight above were hashed on PR 63's tree first and none
    # moved, though every one of them now goes through `layer_kind`'s and `decoder_layer`'s branches
    # for a layer of ONE block, `moe.hidden_rows` (a gated layer's products in the order its three
    # copies wrote them), `ssd_scan`'s groups read from the shapes (granite's kernel at one group is
    # the kernel it was: `tests/test_decoder_nemotron3.py -k traced_equation` holds its body) and
    # `grouped_tiles`' and `rows_as_words`' rules for a width of 14.5 or 10.5 lane tiles
    # (re-pinned in PR 70 with ling3's, above: six `rows_as_words` and six `row_gather` calls where
    # six `gather`s of `x [34816, 2688]` stood)
    "nemotron3_nano_prefill_epix10k2m": "f3a91345e7df789180c0ba3a100203f7f3f440ee80c99a5152047949ed26bd37",
    # pinned in PR 67, which brought it: the nine above were hashed on PR 66's tree first and none
    # moved, though every one of them now goes through `_projections`' rule for the q / k norm (none,
    # a head, the whole projection) and for a block without a norm before its branch, `init_params`'
    # and `decoder_layer`'s branches for the two forms of linear attention and the two norms a branch
    # may have (all taken in Python, before anything is traced); ling3's kernel, whose body this
    # test blanks, is held to the traced equation by `tests/test_decoder_olmo_hybrid.py -k ling3`
    "olmo_hybrid_7b_prefill_epix10k2m": "8cf4e88c84e23a402a343c6010b09e1fee7e07cbc84fc431a1967bd3dcb885da",
    # pinned in PR 78, which added it (the eleventh): DeepSeek-V3's block with each branch between a
    # `hyper_in` and a `hyper_out` (`ops/hyper_connection.py`) and the stream `[T, 4 * D]` between the
    # layers. The twelve other decoders' steps were hashed on the parent's tree and on PR 78's (these ten
    # and minicpm_sala's and phi4flash's, which have no pin): none moved — `latent_attention`, `beside` and
    # `mlp` hand back the branch's output alone where `cfg.hc_mult` says so, read in Python
    "xing4_29b_a4b_prefill_epix10k2m": "e69da6258d104d81442a4ff9963fe9c6b12b7ca96bc4daf942b9ba3ff715c271",
}


@pytest.mark.parametrize("name", sorted(PINNED_STEPS))
def test_the_other_decoders_steps_lower_to_the_programs_they_were(name, one_chip, monkeypatch):
    import hashlib

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = lowered_step(name, one_chip)
    text = lowered.as_text()
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED_STEPS[name]


def test_the_ling3_step_compiles_with_its_kernels_where_the_roofline_functions_count_them(
        one_chip, monkeypatch):
    """The whole served step of ``ling3_flash_prefill_epix10k2m`` at the
    published sizes, compiled for the described v5e (half a minute): it fits
    the chip beside nothing else (weights 10.5 GB), and its Mosaic kernels
    are the ones the cell's roofline metrics read by name or by count: six
    ``gated_delta_rule`` (one a linear layer: ``kda_roofline_share.ling3``
    reads each call), the grouped products under ``moe`` — eighteen in the
    pass ahead of the held rows' loop (``kimi_k2.held_products``'
    ``call_sites``: the ones that RUN on any load under 1.5 even shares) and
    the loop's own eighteen, which run only on what overflows the pass —
    and, since PR 52, the pass's way back, two kernels an expert layer
    (``rows_as_words`` lays the down product's rows out a block each,
    ``sum_counted_rows`` copies a token's counted rows and writes their gated
    sum: with them 30 Pallas instructions run under ``moe``, not ``call_sites``'
    18, so ``gmm_roofline_share.ling3``, which reads every ``pallas_call``
    there, reads nothing, and ``gmm_ahead_roofline_share.ling3`` reads the
    pass's eighteen by their name, ``%gmm``, which this test pins) and,
    since PR 70, the pass's way OUT, two more an expert layer (a second
    ``rows_as_words``, the view of ``x [34816, 2560]`` beside the way back's
    of ``out``, and ``row_gather``, ONE an expert layer: the pass moves 3
    rows a row of ``x``, a turn of the loop 2,048 of 34,816, and
    ``row_gather.tile_rows`` leaves that one to XLA's gather: 42 under ``moe``),
    one ``masked_gqa_attention``, the calibration kernel, since PR 73 six
    ``conv_silu_taps`` (one a linear layer over ``[q | k | v]``, under the scope
    ``conv``: ``conv_ms.ling3`` reads it there), and no other."""
    import collections

    from benchmark.roofline import kimi_k2

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = lowered_step("ling3_flash_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes < 15e9
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    sites = kimi_k2.held_products(cfg["step_tokens"], 8, 2560, 768, 128, 7, 1, 0.25)["call_sites"]
    expert_layers = dcfg.num_layers - dcfg.num_dense_layers
    linear = cfg["layer_types"].count("linear_attention")
    assert names == {"gated_delta_rule": linear, "conv_silu_taps": linear, "gmm": 2 * sites,
                     "rows_as_words": 2 * expert_layers, "row_gather": expert_layers,
                     "sum_counted_rows": expert_layers, "masked_gqa_attention": 1, "fused_calibrate": 1}, names
    assert sites == 3 * expert_layers == 18
    assert all("/moe/" in line for line in calls
               if re.match(r"\s*%(gmm|rows_as_words|row_gather|sum_counted_rows)", line))
    assert all("/kda/" in line for line in calls if re.match(r"\s*%gated_delta_rule", line))
    assert all("/conv/" in line for line in calls if re.match(r"\s*%conv_silu_taps", line))


def test_the_granite_step_compiles_whole_with_its_kernels_under_the_scopes_a_trace_reads(
        one_chip, monkeypatch):
    """The whole served step of ``granite4_h_micro_prefill_epix10k2m`` at the
    published sizes, ALL 40 layers and the whole vocabulary, compiled for the
    described v5e (under half a minute: 36 of the layers are one function at
    one shape, traced and lowered once): it fits the chip (weights 6.4 GB,
    half a GB of temporaries), and its Mosaic kernels are 36 ``ssd_scan`` (one a
    state-space layer, under the scope ``ssd``: ``ssd_roofline_share.granite``
    reads each call by that name), 36 ``conv_silu_taps`` ahead of them (under
    ``conv``, since PR 73), 4 ``masked_gqa_attention`` (under ``sparse_attn``),
    the calibration kernel, and no other."""
    import collections

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = lowered_step("granite4_h_micro_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert 6.3e9 < mem.argument_size_in_bytes < 6.5e9 and mem.temp_size_in_bytes < 1.5e9
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    assert names == {"ssd_scan": cfg["layer_types"].count("mamba") == 36 and 36, "conv_silu_taps": 36,
                     "masked_gqa_attention": cfg["layer_types"].count("attention") == 4 and 4,
                     "fused_calibrate": 1}, names
    assert all("/ssd/" in line for line in calls if re.match(r"\s*%ssd_scan", line))
    assert all("/conv/" in line for line in calls if re.match(r"\s*%conv_silu_taps", line))
    assert all("/sparse_attn/" in line for line in calls if re.match(r"\s*%masked_gqa", line))
    # the stream between the layers is float32 (`decoder.trunk`), every product's operands bf16
    text = compiled.as_text()
    assert re.search(r"f32\[8704,2048\]", text) and not re.search(r"f32\[8704,8192\]\{[^}]*\} dot\(", text)


def test_the_nemotron3_step_compiles_whole_with_nothing_array_sized_between_a_block_s_parts(
        one_chip, monkeypatch):
    """The whole served step of ``nemotron3_nano_prefill_epix10k2m`` at the
    published sizes (fourteen layers of ONE block each, four frames), compiled
    for the described v5e (three quarters of a minute): it fits the chip
    (weights 9.2 GB, 3.1 GB of temporaries), and its Mosaic kernels are six
    ``ssd_scan`` (under ``ssd``), six ``conv_silu_taps`` ahead of them (under
    ``conv``, since PR 73), two ``masked_gqa_attention`` at sixteen heads
    a group (under ``sparse_attn``), under ``moe`` TWO grouped products an
    expert layer in the pass ahead of the held rows' loop and two in the loop
    (``nemotron3.held_products``' ``call_sites``: an ungated expert has no
    gate's product) with the pass's way back (``rows_as_words``,
    ``sum_counted_rows``) and, since PR 70, its way out (a second
    ``rows_as_words``, the view of ``x [34816, 2688]`` whose odd last chunk is
    the low halves of a word, and ``row_gather``, one an expert layer, for the
    156,672 rows XLA's gather moved), the calibration kernel, and no other. Nothing
    array-sized stands between ``W_in``'s products, the convolution, the scan
    and ``W_out``, nor between the rows' gather, the two grouped products and
    the way back: no copy, transpose, slice or pad of ``[34816, 6144 | 4096]``
    or of ``[156672, .]`` (until PR 64 ``sum_counted_rows`` padded a width of
    10.5 lane-tile pairs, 2,688, in a pass of its own). What IS left, and
    named in PERF.md: q relaid head-major for its sixteen heads a group, one
    copy of ``[34816, 2, 16, 128]`` an attention layer."""
    import collections

    from benchmark.roofline import nemotron3

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = lowered_step("nemotron3_nano_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert 9.1e9 < mem.argument_size_in_bytes < 9.3e9 and mem.temp_size_in_bytes < 4e9
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    sites = nemotron3.held_products(cfg["step_tokens"], 6, 2688, 1856, 64, 14, pattern, 0.5)["call_sites"]
    assert names == {"ssd_scan": pattern.count("M"), "conv_silu_taps": pattern.count("M"),
                     "masked_gqa_attention": pattern.count("*"), "gmm": 2 * sites, "rows_as_words": 2 * pattern.count("E"),
                     "row_gather": pattern.count("E"), "sum_counted_rows": pattern.count("E"),
                     "fused_calibrate": 1}, names
    assert sites == 2 * pattern.count("E") == 12
    assert all("/ssd/" in line for line in calls if re.match(r"\s*%ssd_scan", line))
    assert all("/conv/" in line for line in calls if re.match(r"\s*%conv_silu_taps", line))
    assert all("/sparse_attn/" in line for line in calls if re.match(r"\s*%masked_gqa", line))
    assert all("/moe/" in line for line in calls
               if re.match(r"\s*%(gmm|rows_as_words|row_gather|sum_counted_rows)", line))
    entry = text[text.index("ENTRY"):]
    moved = re.findall(r"= \w+\[(?:34816,(?:6144|4096)|4,8704,(?:6144|4096)|156672,\d+)\][^ ]* "
                       r"(?:copy|transpose|slice|pad|concatenate)\(.*", entry)
    assert not moved, [line[:160] for line in moved[:3]]
    assert len(re.findall(r"= bf16\[34816,2,16,128\][^ ]* copy\(", entry)) == pattern.count("*")
    # no held expert's weights are copied: the device lays `w_up [64, 2688, 1856]` out with the
    # contraction minor (`{1,2,0}`: 1,856 is no whole lane tiles) and the grouped product reads it
    # TRANSPOSED, a bitcast (until then a copy of 638 MB at every use, 2.02 ms under no scope)
    assert not re.findall(r"= bf16\[64,(?:2688,1856|1856,2688)\][^ ]* (?:copy|transpose|fusion)\(", text)


def test_the_olmo_hybrid_step_compiles_whole_with_its_kernels_where_the_roofline_functions_count_them(
        one_chip, monkeypatch):
    """The whole served step of ``olmo_hybrid_7b_prefill_epix10k2m`` at the
    published sizes (sixteen layers, one frame), compiled for the described
    v5e (a quarter of a minute: twelve of the layers are one function at one
    shape): it fits the chip (weights 8.2 GB, under a GB of temporaries), and
    its Mosaic kernels are the ones the roofline functions count: twelve
    ``gated_delta_net`` (one a linear layer, under the scope ``gdn``:
    ``olmo_hybrid.delta_rule`` counts a call), 36 ``conv_silu_taps`` ahead of
    them (q's, k's and v's a layer, under ``conv``, since PR 73), four ``masked_gqa_attention``
    (under ``sparse_attn``: ``olmo_hybrid.causal_attention``) at TWO heads a
    grid step (30 heads alone in their groups, unturned: 15 x 36 grid steps a
    call), the calibration kernel, and no other. q and k leave their
    products a head at whole lane tiles (``[8704, 3840]`` for 30 heads of 96:
    the pad is on the 11 M-element WEIGHT) and nothing array-sized stands
    between those products, the convolutions, the kernel and ``W_o``: no
    copy, slice, pad or transpose of a bf16 ``[8704, 2880 | 3840 | 5760]``."""
    import collections

    from psana_ray_tpu.parallel import sparse_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = lowered_step("olmo_hybrid_7b_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert 8.1e9 < mem.argument_size_in_bytes < 8.3e9 and mem.temp_size_in_bytes < 1.5e9
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    assert names == {"gated_delta_net": cfg["layer_types"].count("linear_attention") == 12 and 12,
                     "conv_silu_taps": 3 * 12, "masked_gqa_attention": cfg["layer_types"].count("full_attention") == 4 and 4,
                     "fused_calibrate": 1}, names
    assert all("/gdn/" in line for line in calls if re.match(r"\s*%gated_delta_net", line))
    assert all("/conv/" in line for line in calls if re.match(r"\s*%conv_silu_taps", line))
    assert all("/sparse_attn/" in line for line in calls if re.match(r"\s*%masked_gqa", line))
    assert sa.heads_a_step(30, 1, 1088, 1088, 128, 128) == 2
    from psana_ray_tpu.models import decoder

    assert decoder.causal_call_steps(dcfg, 3, 1, 8704) == (30 * 36, 15 * 36, 15 * 36)  # what the step counts
    entry = text[text.index("ENTRY"):]
    moved = re.findall(r"= bf16\[8704,(?:2880|3840|5760)\][^ ]* (?:copy|transpose|slice|pad|concatenate)\(.*",
                       entry)
    assert not moved, [line[:160] for line in moved[:3]]
    # what IS left, and named in PERF.md section 7 (5): a full layer's q and k products leave their
    # fusion float32 and COLUMN-major (XLA's choice for the whole-projection norm's row sums in the
    # product's epilogue) and are copied row-major before the norm scales them: two a full layer
    assert len(re.findall(r"= f32\[8704,3840\][^ ]* copy\(", entry)) == 2 * 4
    assert len(re.findall(r"= bf16\[3840,30,128\][^ ]* pad\(", entry)) == 2 * 12  # W_q's and W_k's


def test_the_minicpm_sala_step_compiles_whole_with_its_kernels_under_the_scopes_a_trace_reads(
        one_chip, monkeypatch):
    """The whole served step of ``minicpm_sala_prefill_epix10k2m`` at the
    published sizes (four layers, one frame of 34,304 tokens), compiled for the
    described v5e (under ten seconds): it fits the chip (weights 3.4 GB, 4 GB
    of temporaries: a float32 stream and the MLP's 16,384-wide rows), and its
    Mosaic kernels are the ones the roofline functions count, each under the
    scope a trace reads: ONE ``select_blocks`` (under ``block_select``:
    ``minicpm_sala.select_blocks``), ONE ``masked_gqa_attention`` under its flags
    (``sparse_attn``: ``minicpm_sala.sparse_attention``), three
    ``lightning_attention`` (``lightning``: ``minicpm_sala.lightning_attention``),
    the calibration kernel, and no other. The sparse layer's calls meet shapes
    no other cell compiles: sixteen heads a group UNDER a mask (a query tile of
    128 x 2,048 keys, the keys padded to seventeen tiles), two selections a
    layer from ``[2, 34304, 640]`` flags."""
    import collections

    from psana_ray_tpu.models import decoder
    from psana_ray_tpu.parallel import sparse_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = lowered_step("minicpm_sala_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert 3.4e9 < mem.argument_size_in_bytes < 3.5e9 and mem.temp_size_in_bytes < 4.5e9
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    assert names == {"select_blocks": 1, "masked_gqa_attention": 1, "lightning_attention": 3,
                     "fused_calibrate": 1}, names
    for kernel, scope in (("select_blocks", "block_select"), ("masked_gqa", "sparse_attn"),
                          ("lightning_attention", "lightning")):
        assert all(f"/{scope}/" in line for line in calls if re.match(rf"\s*%{kernel}", line))
    assert re.search(r"s8\[2,34304,640\]", text)  # the flags, a key head each
    sel = dcfg.block_select
    assert sel.tiles(34304) == (2048, 32, 640)
    assert sa._masked_query_tile(34304, dcfg.attn_q_tile, 128, 16 * 2048) == 128
    assert decoder.causal_call_steps(dcfg, 1, 1, 34304) == (0, 0, 0)  # a linear layer makes no causal call


def test_the_ouro_step_compiles_whole_as_one_loop_around_one_stack_of_layers(one_chip, monkeypatch):
    """The whole served step of ``ouro_2p6b_prefill_epix10k2m`` at the published
    sizes, ALL 48 layers four times over and the whole vocabulary, compiled for
    the described v5e (a quarter of a minute: the 48 layers are one function at
    one shape, traced and lowered once, and the four passes are a loop IN the
    program): it fits the chip with the weights held ONCE (5.34 GB of
    arguments, a third of a GB of temporaries), holds ONE ``while``, whose body
    has the 48 ``masked_gqa_attention`` call sites (under ``sparse_attn``; the
    calibration kernel stands outside) and no copy of a weight."""

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = lowered_step("ouro_2p6b_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert 5.3e9 < mem.argument_size_in_bytes < 5.4e9 and mem.temp_size_in_bytes < 0.6e9
    text = compiled.as_text()
    loops = re.findall(r" while\(.*body=%?([\w.\-]+)", text)
    assert len(loops) == 1, loops
    start = text.index("%" + loops[0] + " (")
    body = text[start:text.index("\n}\n", start)].splitlines()
    calls = [line for line in body if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == dcfg.num_layers == 48 and text.count('custom_call_target="tpu_custom_call"') == 49
    assert all(re.match(r"\s*%masked_gqa_attention", line) and "/sparse_attn/" in line for line in calls)
    assert any("/pass_end/" in line for line in body)
    # the weights are the loop's invariants: nothing in the body copies, transposes or converts one
    weights = {tuple(a.shape) for a in jax.tree.leaves(decoder_cell("ouro_2p6b_prefill_epix10k2m")[2])
               if a.ndim == 2}
    moved = [line for line in body
             if (m := re.match(r"\s*%[\w.\-]+ = bf16\[([\d,]+)\]\S* (copy|transpose|convert)\(", line))
             and tuple(int(n) for n in m.group(1).split(",")) in weights | {w[::-1] for w in weights}]
    assert not moved, moved[:3]


def test_the_laguna_step_compiles_with_its_kernels_where_the_roofline_functions_count_them(
        one_chip, monkeypatch):
    """The whole served step of ``laguna_s21_prefill_epix10k2m`` at the
    published sizes, compiled for the described v5e: it fits the chip beside
    nothing else (weights 11.4 GB), and its Mosaic kernels are the ones the
    cell's roofline metrics read by name: three ``masked_gqa_attention`` (the
    full layers, 6 query heads of 128 a group, under ``sparse_attn``), six
    ``windowed_gqa_attention`` (the same body in ONE step a query tile against
    a key window that follows the diagonal, 9 a group, under ``window_attn``),
    the grouped products under ``moe`` —
    twenty-four in the pass ahead of the held rows' loop (``call_sites``,
    named ``gmm``) and the loop's own twenty-four (named after the jit the
    loop stands in) — and the pass's way back at TEN slots a token
    and 24 lane chunks a row (``rows_as_words``, ``sum_counted_rows``, whose
    step of 5,120 slots takes 60 MB of VMEM for its two buffers), the
    calibration kernel, and no other: the rows go OUT by XLA's gather, and
    since PR 70 by the row gather's own rule, not for their width (3,072 is
    24 whole chunks, which the kernel takes through ``rows_as_words``' view):
    ``x [17408, 3072]`` is 102 MiB, which XLA's gather keeps in vector memory
    and moves at 10 ns a row, 0.61 ms a layer where the kernel with its view
    reads 1.71 (``row_gather.tile_rows``; my chip runs, PR 70)."""
    import collections

    from benchmark.roofline import laguna

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # as every entry point compiles (`jaxenv.configure_compile_cache`): locations of one frame, under
    # which an instruction is named after the jitted function it was traced in. The windowed layers'
    # calls are jitted under `sparse_attention.windowed_gqa_attention` for that: on the v5e all nine
    # kernels of a step carried the full layers' name while one jit served both (PR 53)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    cfg, dcfg, lowered = lowered_step("laguna_s21_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes < 15e9
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    sites = laguna.held_products(cfg["step_tokens"], 10, 3072, 1024, 64, 9, [0], 0.25)["call_sites"]
    expert_layers = dcfg.num_layers - dcfg.num_dense_layers
    # by the jitted function each was traced in: the pass's products `gmm` (what
    # `gmm_ahead_roofline_share.laguna` reads by name), the loop's after the jit the loop stands in,
    # both kernels of the way back after `sum_counted_rows`
    assert names == {"masked_gqa_attention": cfg["layer_types"].count("full_attention"),
                     "windowed_gqa_attention": cfg["layer_types"].count("sliding_attention"),
                     "gmm": sites, "mlp": sites, "sum_counted_rows": 2 * expert_layers,
                     "fused_calibrate": 1}, names
    assert sites == 3 * expert_layers == 24 and names["masked_gqa_attention"] == 3
    assert all("/moe/" in line for line in calls if re.match(r"\s*%(gmm|mlp|sum_counted_rows)", line))
    assert all("/sparse_attn/" in line for line in calls if re.match(r"\s*%masked_gqa", line))
    assert all("/window_attn/" in line for line in calls if re.match(r"\s*%windowed_gqa", line))
    text = compiled.as_text()
    for scope in ("proj", "shared_expert", "mlp", "moe", "sparse_attn", "window_attn"):
        assert f"jit(step)/{scope}/" in text, scope


def test_every_latent_layer_of_kimi_s_step_hands_the_kernel_what_its_product_wrote(
        one_chip, monkeypatch):
    """The WHOLE served step of ``kimi_k2_prefill_epix10k2m``, compiled for
    the described v5e (a minute): what the layer alone (above) cannot see.
    With ``W_uq``'s rotary product written two-dimensional and reshaped
    (PR 61's first form) the layer alone and layer 0 of the step compiled
    to ONE product writing the kernel's operand, and layers 1-6 of the
    step to a COLUMN-major product ``f32[17408,4096]{0,1}`` and a ``copy``
    of it into ``[17408,64,1,64]`` (8.4 ms of the step on the chip, under
    ``latent_attn``): here every one of the seven kernels' rotary query is,
    through bitcasts alone, a product's own result; the angle tables are
    made once a step, and no float32 half of a rotary query exists."""

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = lowered_step("kimi_k2_prefill_epix10k2m", one_chip)
    text = lowered.compile().as_text()
    entry = text[text.index("ENTRY"):]
    made = {m.group(1): (m.group(2), line) for line in entry.splitlines()
            for m in [re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \S+ ([\w\-]+)\(", line)] if m}
    tokens, heads, dr = cfg["batch_size"] * 8704, dcfg.num_heads, dcfg.qk_rope_head_dim
    kernels = [line for name, (_, line) in made.items() if name.startswith("%masked_gqa_attention")]
    assert len(kernels) == dcfg.num_layers == 7
    for line in kernels:
        operands = re.findall(r"%[\w.\-]+", line.split("custom-call(")[1].split(")")[0])
        rotary = [o for o in operands if f"f32[{heads},1,{tokens},{dr}]" in made[o][1]]
        assert len(rotary) == 1, operands
        name = rotary[0]
        while made[name][0] == "bitcast":
            name = re.search(r"bitcast\((%[\w.\-]+)\)", made[name][1]).group(1)
        assert made[name][0] == "fusion" and "convolution" in name, made[name][1][:200]
    assert f"f32[{tokens},{heads},{dr // 2}]" not in entry
    assert sum("jit(turn_tables)" in line for _, line in made.values()) == 2  # [cos|cos], [sin|sin]




def _xing4_step():
    """``xing4_29b_a4b_prefill_epix10k2m``'s served step at the published sizes
    (``tests/chip.py``'s ``compiled``: once a worker)."""
    import jax.numpy as jnp

    from chip import F32, H, PANELS, S, W
    from psana_ray_tpu.models import decoder

    cfg, dcfg, params = decoder_cell("xing4_29b_a4b_prefill_epix10k2m")
    calib = (S((PANELS, H, W), F32), S((PANELS, H, W), F32), S((PANELS, H, W), jnp.uint8))
    frames = S((cfg["batch_size"], PANELS, H, W), jnp.uint16)

    def step(p, c, f, i):
        return decoder.frame_step(p, c, f, i, cfg=dcfg, threshold=10.0)

    return step, (params, calib, frames, S((cfg["prompt_tokens"],), jnp.int32)), cfg, dcfg


def test_the_xing4_step_compiles_whole_with_its_mixes_in_two_kernels_a_branch(one_chip, monkeypatch):
    """The whole served step of ``xing4_29b_a4b_prefill_epix10k2m`` (layers 0-7
    of 40, every expert held, all 131,072 ids) compiled for the described v5e
    (45 s): it fits the chip under the 14.5 GB the configuration's rule for its
    DEPTH names (weights 11.36 GB; over that, the file takes layers 0-6); each
    of the sixteen branches' mixes is ONE ``hyper_in`` and ONE ``hyper_out``
    under the scopes a trace reads them by; the stream between the layers is
    bf16 ``[17408, 14336]`` and NO float32 array of that size exists anywhere
    (left to XLA the wide norm alone made one); the mixing numbers a kernel
    hands the other are float32 ``[17408, 128]``; kimi's latent kernel runs
    once a layer and the grouped product three times an expert layer, where the
    roofline functions count them."""
    import collections

    from chip import compiled

    text, (arguments, outputs, temporaries), (cfg, dcfg) = compiled(_xing4_step, one_chip, monkeypatch)
    assert arguments + outputs + temporaries < 14.5e9 and 11.3e9 < arguments < 11.4e9
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    branches, expert_layers = 2 * dcfg.num_layers, dcfg.num_layers - dcfg.num_dense_layers
    assert names == {"hyper_in": branches, "hyper_out": branches, "masked_gqa_attention": dcfg.num_layers,
                     "gmm": 3 * expert_layers, "rows_as_words": expert_layers,
                     "row_gather": expert_layers, "fused_calibrate": 1}, names
    assert all("/hyper_in/" in line for line in calls if re.match(r"\s*%hyper_in", line))
    assert all("/hyper_out/" in line for line in calls if re.match(r"\s*%hyper_out", line))
    tokens, wide = cfg["step_tokens"], dcfg.hc_mult * dcfg.hidden_size
    assert (tokens, wide) == (17408, 14336)
    entry = text[text.index("ENTRY"):]  # what the step WRITES (a fusion's own body may widen a value it reads)
    made = [m.group(1) for line in entry.splitlines()
            for m in [re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\w+\[[\d,]*\])", line)] if m]
    assert f"bf16[{tokens},{wide}]" in made and f"f32[{tokens},{wide}]" not in made
    assert f"f32[{tokens},{dcfg.hc_mult},{dcfg.hidden_size}]" not in made
    handed = [line for line in calls if re.match(r"\s*%hyper_out", line)]
    assert all(f"f32[{tokens},128]" in line for line in handed)  # the mixing numbers: float32
