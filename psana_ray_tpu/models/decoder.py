"""A config-driven decoder block and trunk, and the frame reader built on it.

Nothing else in ``models/`` has an RMS norm, a rotary embedding, a gated
MLP or a block assembled from a configuration (``vit.py`` hard-codes
LayerNorm, GELU and a position table). This module builds the text
decoder of a language model from the keys of its published
``config.json`` (Keye-VL-2.0's text decoder, LFM2-8B-A1B, DeepSeek-V3's
block as Kimi-K2 spells it, DeepSeek-V3.2's, Ling-3.0's hybrid,
Laguna-S-2.1's windowed and full layers, Granite-4.0-H's state-space hybrid,
Ouro's looped stack, Nemotron-H's layers of one block each, Olmo-Hybrid's
Gated DeltaNet layers under the reordered norm, MiniCPM-SALA's block-sparse
and fixed-decay linear layers under MiniCPM's three multipliers,
Phi-4-mini-flash's decoder-hybrid-decoder: Mamba-1 scans, differential
attention, and layers that read what an earlier layer made):

    x  -> x + op(norm(x))                     pre-norm; a layer's op is one of
    x  -> x + ff(norm(x))                     its kinds, its ff one of two

(``norm`` an RMS norm with a gain or, where the configuration says so, a
LayerNorm with a gain and a bias: :func:`block_norm`;
a branch times ``residual_multiplier`` before it is added, where a model has
one; a branch normed AGAIN before it is added under ``sandwich``, and with
``pre_norm`` off normed ONLY then, ``x + rms(op(x))``: OLMo 2's reordered
norm, one rule for which norms a branch has). Where the configuration says that a layer is ONE block
(``single_block``: Nemotron-H's ``hybrid_override_pattern``) a layer is
EITHER line, an operator alone or a feed-forward alone, with the one norm
that line has: ``layer_kind`` names what a layer has and ``decoder_layer``
runs what the kind names.

A layer's OPERATOR (``layer_types``) is grouped-query attention (an RMS
norm on q and k where the model has one, a head or over the whole
projection, then the rotary,
multimodal or on the sequence index, over all of a head or its leading
part; full causal, or SLIDING: over the band of the ``sliding_window``
latest keys, with the layer's own count of query heads and its layer
type's rotary, and where the configuration says so a sigmoid gate a head
on the output); or a gated short convolution (:func:`gated_short_conv`);
or LINEAR attention (:func:`linear_attention`: the gated delta rule with a
decay per channel, or with ONE decay a head over keys and values of their own
widths, a float32 state a head carried along the sequence:
``ops/delta_rule.py``; or, with no delta rule at all, :func:`fixed_decay_attention`:
a decay that is a CONSTANT of the head, q and k normed a head and turned by
a rotary inside the kernel: ``ops/lightning.py``); or a STATE-SPACE layer of one of TWO kinds:
Mamba-2's (:func:`state_space`: one scalar decay a head and token, keys and
queries shared by all heads or by the heads of each of ``ssm_groups`` groups,
the gated norm by those groups, a float32 state a head carried along the
sequence, a chunk a matrix product: ``ops/ssd.py``) or Mamba-1's
(:func:`selective_state_space`: a decay a CHANNEL and STATE, the step a
channel through a low rank, a first-order recurrence an element with no
matrix form: ``ops/selective_scan.py``); or a layer that READS WHAT AN
EARLIER LAYER MADE (the stack hands it on beside ``x``:
``DecoderConfig.read_from``): a gated memory unit (:func:`gated_memory`: a
gate of its own times an earlier scan's output at the same token) or cross
attention (queries of its own against an earlier layer's keys and values).
Grouped-query attention may be DIFFERENTIAL (:func:`diff_attention`: heads in
pairs, two softmaxes a pair whose outputs are subtracted under a learned
lambda and normed);
or, where the configuration has a ``kv_lora_rank``, LATENT attention
(:func:`latent_attention`: queries of full rank or through a normed low
rank, keys and values decompressed per head from one normed latent, one
rotary key for all heads, plain or YaRN's frequencies, and where the
configuration says so a sigmoid gate a head on the output). Either attention is plain causal or restricted, per query
and in every head alike, to the ``topk`` keys a learned INDEXER ranks
highest (``parallel/sparse_attention.py``; :func:`_indexer`): its queries
are projected from the layer's normed input or, under latent attention,
from the query's normed low rank; its one key goes through an RMS norm
or a LayerNorm, and the rotary turns all of an index vector or its
leading part (fields, with the first indexer's values as defaults); or,
WITHOUT an indexer, grouped-query attention restricted a KEY HEAD to the
blocks of keys its own queries score highest against the mean-pooled keys
(``block_select``: ``sparse_attention.select_blocks``, in sequences longer
than its ``dense_len``), under an elementwise sigmoid gate where the
configuration says so. Its
FEED-FORWARD is a dense MLP (the first ``num_dense_layers``, or all where
there are no experts; gated SiLU, or under ``mlp_act`` ``relu2`` the UNGATED
``relu(x W_up)^2 W_down``, as the experts and the shared expert then are:
``moe.hidden_rows``) or top-k of ``num_experts`` experts
without dropped tokens (``parallel/moe.dropless_moe``; a softmax router,
or sigmoid affinities under a selection bias, the choice limited to the
best ``router_groups_kept`` of ``router_groups`` groups where there are
groups), of which this holder may hold a share (``experts_held``: only
the held slots' rows move), beside ``shared_experts`` that every token
passes through. The trunk runs that schedule over ``B``
sequences of ``S`` tokens held as ``[B*S, D]`` rows: what mixes tokens
(attention, the convolution, the rotary) is told ``B`` and stays inside
a sequence; the expert layer sorts all ``B*S`` rows at once and moves each
row once each way: one in-bounds gather into expert order
(``ops/row_gather.py``), and on the way back a token's ``k`` rows read
where the sort put them and summed under their gates in one pass, in the
order of the token's choices, so a sequence's rows do not depend on its
place in the batch.

:func:`frame_step` is the serving step of a FRAME READER: ``B`` detector
frames, calibrated on the device, cut into patches, embedded by a linear
patch embedding (standing in for a vision tower), each followed by the
text prompt, read through the trunk; the logits of each frame's next token
come back with a small statistics vector (:data:`STEP_STATS`) that
:func:`fold_step_stats` adds to a pipeline's counters. Where a schedule ENDS
in layers that mix no tokens (``DecoderConfig.cut_layer``) the step runs
them on the rows it serves alone (:func:`trunk`'s ``rows``). Weights are an
ARGUMENT of the step: one step keys alike in the compile cache from every
entry point.

Not imported by ``psana_ray_tpu`` nor ``psana_ray_tpu.models`` at package
import: the serving CLIs that never read a frame with it do not pay for it.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from psana_ray_tpu.ops import lightning
from psana_ray_tpu.ops import hyper_connection as hc
from psana_ray_tpu.ops.delta_rule import (CHUNK, HEAD_CHUNK, chunk_rows, gated_delta_net,
                                          gated_delta_rule, lanes_a_head)
from psana_ray_tpu.ops.selective_scan import LANES as SCAN_LANES, scan_tiles, selective_scan
from psana_ray_tpu.ops.short_conv import conv_silu_taps, gated_conv_taps
from psana_ray_tpu.ops.ssd import scan_rows, ssd_scan
from psana_ray_tpu.parallel import sparse_attention as sa
from psana_ray_tpu.parallel.moe import dropless_moe, goes_ahead, hidden_rows, rows_ahead

# what frame_step's statistics vector holds: the first four summed over the
# batch and over the layers that have the thing counted
STEP_STATS = (
    "expert_tokens_max_total",   # the busiest held expert's token slots
    "expert_tokens_mean_total",  # token slots per expert, were the load even: B * S * k / E
    "attn_tiles_live_total",     # 512 x 512 tiles at or below the diagonal with an attended pair
    "attn_tiles_causal_total",   # all such tiles
    "decoder_tokens_total",      # tokens the step served: B * S
    "decoder_sequences_total",   # sequences (frames) the step served: B
)
# two more, after those six, from a holder of a SHARE of the experts (where
# every expert is held they would say the same thing twice, and the step is
# the program it was)
SHARE_STATS = (
    "expert_rows_held_total",    # token slots whose expert lives here, over the expert layers
    "expert_rows_routed_total",  # all token slots of those layers: B * S * k each
)
# and two after those eight, from a key selection over LATENT attention: what
# the selection kept of what it could. |Sel(t)| = min(t + 1, topk) exactly
# (`select_keys` cuts ties to it), so both are counted from the shapes
PAIR_STATS = (
    "attn_pairs_selected_total",  # (query, key) pairs attended, over the layers with a selection
    "attn_pairs_causal_total",    # pairs at or below the diagonal in those layers
)
# and two after those ten (the two above 0 where nothing selects), where layers CARRY A STATE
# along the sequence: LINEAR (the delta rule) or, since PR 57, MAMBA (the state-space scan). No new
# name and no new length: a step with either kind returns these twelve
LINEAR_STATS = (
    "linear_attn_tokens_total",  # tokens through a layer that carries a state, over such layers
    "linear_attn_chunks_total",  # chunks of the layer's kernel: head-sequences x chunks a layer
)
# and one after those twelve (the groups above 0 where the step has none), from a holder of so LARGE
# a share that a pass goes ahead of its held rows' loop (`moe.rows_ahead`)
AHEAD_STATS = (
    "expert_rows_ahead_total",   # held rows that pass, and its way back, took: the loop took the rest
)
# and two after those thirteen (the groups above 0 where the step has none), where ONE stack of
# layers is run `passes` times over with the same weights (a looped model: `DecoderConfig.passes`)
LOOP_STATS = (
    "loop_passes_total",         # passes through the stack: `passes` a step
    "exit_pass_sum",             # over the step's frames, sum_r r * p_r at the frame's last token: the
                                 # pass the exit gate would have left at, in expectation (/ frames)
)
# and two after those fifteen (the groups above 0 where the step has none), ONLY from a step whose
# causal calls take a BLOCK of heads a grid step (`sparse_attention.heads_a_step`: heads alone in their
# groups, latent attention's): constants of the shapes, their quotient the heads a grid step
BLOCK_STATS = (
    "attn_head_tiles_total",     # (head, query tile, key tile) visits of the step's causal calls
                                 # (a group's stacked heads, which share their keys, count as one)
    "attn_grid_steps_total",     # the grid steps those calls took: a block of heads' visits each
)
# and two after those seventeen (the groups above 0 where the step has none), ONLY from a step whose
# LATER layers ran on fewer rows than its earlier ones (`trunk`'s `rows`, where the schedule ends in
# layers that mix no tokens: `DecoderConfig.cut_layer`): their quotient names the regime a step was
# timed in (every other step runs every layer on every row, and has no such counters)
ROWS_STATS = (
    "trunk_rows_run_total",      # over the layers, the rows the layer ran
    "trunk_rows_full_total",     # layers x B * S: every layer on every row
)
# and one after those nineteen (the groups above 0 where the step has none, BLOCK_STATS' two at what
# its calls take), ONLY from a step in which some causal call cuts a STACKED group's rows into parts
# (`sparse_attention.parts_a_step`: heads that share their keys): a constant of the shapes
PART_STATS = (
    "attn_part_tiles_total",     # (part, query tile, key tile) score products the step's causal calls
                                 # write: over `attn_grid_steps_total`, the parts a grid step
)
# and two after those twenty (the groups above 0 where the step has none), ONLY from a step whose stream
# between the layers is SEVERAL rows a token (`DecoderConfig.hc_mult`: hyper-connections). The second is
# a MAXIMUM, over the step's tokens and branches and, folded, over the steps (`MAX_STATS`)
HYPER_STATS = (
    "hyper_mixes_total",         # (branch, token) pairs mixed in and out: 2 * layers * B * S
    "hyper_sum_defect_max",      # the largest distance of a row or column sum of any H_res from 1: what
                                 # says that the doubly stochastic constraint HELD on the served tokens
)
MAX_STATS = ("hyper_sum_defect_max",)  # folded by maximum, not summed
# the places the LAYERS count at most: the first four of STEP_STATS and the four groups a layer has
LAYER_GROUPS = 4 + len(SHARE_STATS + PAIR_STATS + LINEAR_STATS + AHEAD_STATS)
# layer_types, as config.json spells them
ATTENTION, CONV, LINEAR = "full_attention", "conv", "linear_attention"
SLIDING = "sliding_attention"  # grouped-query attention over the band t - sliding_window < j <= t
# the two kinds of STATE-SPACE layer: Mamba-2's (granitemoehybrid's spelling; its "attention" is
# ATTENTION), ONE scalar decay a head and token, a chunk a matrix product (`ops/ssd.py`), and
# Mamba-1's, a decay a CHANNEL and STATE, a recurrence an element (`ops/selective_scan.py`)
MAMBA = "mamba"
MAMBA1 = "mamba1"
# two kinds of layer that read what an EARLIER layer made (`DecoderConfig.cut_layer`): a gated memory
# unit reads the nearest earlier MAMBA1 layer's scan output at its own token; CROSS attention has
# queries of its own and reads the keys and values of the nearest earlier ATTENTION layer
GMU, CROSS = "gated_memory", "cross_attention"
LATENT = "latent_attention"  # what an ATTENTION layer is under a kv_lora_rank
# a layer that is ONE block and has no operator names its feed-forward (`DecoderConfig.single_block`)
EXPERTS, DENSE = "moe", "mlp"
# a `hybrid_override_pattern`'s letters (nemotron_h's spelling of a schedule of single blocks)
PATTERN = {"M": MAMBA, "*": ATTENTION, "E": EXPERTS, "-": DENSE}
# a `mixer_types`' words (minicpm_sala's spelling of a schedule): grouped-query attention under the
# file's block selection, and linear attention with a fixed decay a head
MIXERS = {"minicpm4": ATTENTION, "lightning-attn": LINEAR}


@dataclasses.dataclass(frozen=True)
class Yarn:
    """``rope_scaling`` of type ``yarn``, as DeepSeek-V3's code reads it."""

    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _mscale(factor: float, m: float) -> float:
        return 0.1 * m * float(np.log(factor)) + 1.0 if factor > 1 else 1.0

    @property
    def rotary_scale(self) -> float:
        """What the cosines and sines are multiplied by."""
        return self._mscale(self.factor, self.mscale) / self._mscale(self.factor, self.mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        """``mscale**2``: what the softmax scale ``d**-0.5`` is multiplied by."""
        return self._mscale(self.factor, self.mscale_all_dim) ** 2

    def inv_freq(self, theta: float, pairs: int) -> np.ndarray:
        """``[pairs]`` float64: pair ``i`` keeps ``theta**(-i/pairs)`` where
        the ramp between the two correction dimensions is 0 and takes
        that over ``factor`` where it is 1, blended in between."""
        def correction(turns):  # the pair that makes `turns` turns over the original positions
            return (2 * pairs * np.log(self.original_positions / (turns * 2 * np.pi))
                    / (2 * np.log(theta)))

        low = max(int(np.floor(correction(self.beta_fast))), 0)
        high = min(int(np.ceil(correction(self.beta_slow))), 2 * pairs - 1)
        ramp = np.clip((np.arange(pairs) - low) / ((high + 0.001 if low == high else high) - low),
                       0.0, 1.0)
        plain = theta ** (-np.arange(pairs, dtype=np.float64) / pairs)
        return plain / self.factor * ramp + plain * (1.0 - ramp)


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    vocab_size: int
    rms_eps: float
    rope_theta: float
    # multimodal rotary over (t, h, w) positions; None: plain rotary on the sequence index
    mrope_section: Optional[Tuple[int, int, int]] = None
    # each layer's operator, ATTENTION, SLIDING, CONV or LINEAR (empty: attention in every layer)
    layer_types: Tuple[str, ...] = ()
    # a SLIDING layer's band: a query attends to its own key and the sliding_window - 1 before it
    sliding_window: int = 0
    # each layer's query heads where layers differ (num_attention_heads_per_layer: wq, wo and the
    # output gate are that many heads wide; empty: num_heads in all)
    heads_per_layer: Tuple[int, ...] = ()
    # the rotary is the LAYER TYPE's (rope_parameters): rope_theta and rope_yarn are the FULL
    # layers', and turn the leading rope_partial_dim of a grouped-query head (0: all of it, the
    # rest passes as it is); a SLIDING layer's is plain, over the whole head, at sliding_rope_theta
    rope_partial_dim: int = 0
    sliding_rope_theta: float = 0.0
    qk_norm: bool = True  # an RMS norm with a gain on each grouped-query head's query and key,
    # or ("projection") on ALL of a token's query columns and all its key columns, before the heads are cut
    qk_norm_span: str = "head"
    conv_taps: int = 3  # of a short convolution (conv_L_cache, short_conv_kernel_size)
    # linear attention (the gated delta rule with a decay per channel): num_heads heads whose keys
    # and values are linear_head_dim wide, the log-decay a token in (linear_decay_floor, 0)
    linear_head_dim: int = 0
    linear_decay_floor: float = 0.0
    # the same operator's OTHER form (Gated DeltaNet's): values linear_value_dim wide (0: as wide as
    # the keys, a square state), ONE decay a head and token, -exp(A) softplus(a + b), unbounded below
    # ("head"; "channel": the bounded gate above), the step size sigmoid(.) times linear_beta_scale
    # (2: a state's eigenvalues may be negative). `linear_decay` ALONE says which form a layer is: what
    # is drawn for it, which kernel runs it, and the output's gate (silu per head, sigmoid per channel)
    linear_value_dim: int = 0
    linear_decay: str = "channel"
    linear_beta_scale: float = 1.0
    # and its THIRD ("fixed"): no delta rule at all, S_t = lambda_h S_{t-1} + k_t v_t^T with a decay that
    # is a CONSTANT of the head (Lightning Attention-2's slopes: `ops/lightning.decay_slopes`), q and k
    # normed a head and, under `linear_rotary`, turned by the plain rotary at rope_theta over the whole
    # head; the output normed a head and THEN gated by a sigmoid (`ops/lightning.py`)
    linear_rotary: bool = False
    # a MAMBA layer's scan: ssm_heads heads of ssm_head_dim channels over a state ssm_state wide,
    # B and C shared by the heads of each of ssm_groups groups of consecutive heads, which are also
    # the groups the gated norm goes by; conv_bias: its convolution adds a bias per channel
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    conv_bias: bool = False
    # a MAMBA1 layer's scan: scan_channels channels (d_inner) over the same ssm_state, the step a
    # channel through a low rank of scan_dt_rank; its convolution's taps and bias as above
    scan_channels: int = 0
    scan_dt_rank: int = 0
    # the blocks' and the final norm: "rms" (a gain) or "layer" (LayerNorm: a gain and a bias)
    norm: str = "rms"
    attn_bias: bool = False  # a bias on an attention layer's products (W_qkv or W_q, and W_o)
    # DIFFERENTIAL attention (arXiv:2410.05258) in every ATTENTION, SLIDING and CROSS layer: heads in
    # PAIRS, a pair's two half-heads each a softmax of its own over the pair's values (both halves'
    # side by side: twice head_dim wide), the second subtracted times a learned lambda, the
    # difference RMS-normed over the pair's columns with a gain and scaled by 1 - lambda_init
    diff_attention: bool = False
    # every layer is ONE block, x + Mixer(rms(x)): an operator ALONE (no feed-forward after it) or,
    # where layer_types names EXPERTS or DENSE, a feed-forward alone (no operator before it)
    single_block: bool = False
    # an expert's, the shared expert's and a dense MLP's hidden activation: "silu" is the gated
    # silu(x W_gate) * (x W_up); "relu2" the UNGATED relu(x W_up)^2, which has no W_gate
    mlp_act: str = "silu"
    # Granite's four multipliers, each defaulting to what every other model has: a branch's output
    # times residual_multiplier before it is added, the embedded rows times embedding_multiplier,
    # the softmax scale (None: head_dim ** -0.5), the logits over logits_scaling
    residual_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    rotary: bool = True  # False (position_embedding_type nope): q and k are not turned at all
    tie_embedding: bool = False  # the output head is the embedding table
    # a LOOPED model (Ouro's): the one stack of layers is run `passes` times over with the SAME
    # weights, the final norm at the end of EVERY pass and its rows what the next pass starts from
    # (the head then reads the last pass's normed rows as they are); under `exit_gate`
    # lambda_r = sigmoid(h_r w_e + b_e) a token and pass gives the exit distribution
    # (:func:`exit_distribution`; the step runs every pass whatever it says: early_exit_threshold 1).
    # `sandwich`: a branch is normed AGAIN before it is added, x + rms(Op(rms(x))): four gains a layer
    passes: int = 1
    sandwich: bool = False
    exit_gate: bool = False
    # which norms a BRANCH has, the rule for every block: one BEFORE it (`pre_norm`: x + Op(rms(x)))
    # and/or one AFTER it (`sandwich`'s second: x + rms(Op(.))). Both: the sandwich; the second alone
    # (pre_norm False): OLMo 2's REORDERED norm, x + rms(Op(x)), where nothing norms a branch's input
    pre_norm: bool = True
    # HYPER-CONNECTIONS, manifold-constrained (0: the plain residual, one row a token): the stream is
    # hc_mult rows of hidden_size a token, [T, hc_mult * D]; a branch reads a MIX of them and its output
    # goes onto ANOTHER mix (`ops/hyper_connection.py`: the mixing numbers a token and branch from the
    # stream's own wide norm, the stream-to-stream matrix projected onto the doubly stochastic ones by
    # hc_iters Sinkhorn steps under sums guarded by hc_eps, its logits clamped to hc_clamp first)
    hc_mult: int = 0
    hc_iters: int = 0
    hc_eps: float = 0.0
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    rope_yarn: Optional[Yarn] = None  # YaRN-blended rotary frequencies and the softmax's mscale^2
    # latent attention (kv_lora_rank 0: grouped-query attention): queries through a normed
    # q_lora_rank (0: of full rank), keys and values from ONE normed kv_lora_rank latent; a head's query and key
    # are [qk_nope_head_dim | qk_rope_head_dim] (head_dim is their sum), the rotary part of the
    # key one for all heads; values v_head_dim wide
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # "head_wise": either attention's output times sigmoid(a W_G), one scalar a head and token;
    # "elementwise": grouped-query attention's times sigmoid(a W_G) [T, H * head_dim], a scalar a column
    attn_gate: str = ""
    # learned sparse attention (None: plain causal attention). The index queries are projected
    # from the layer's normed input, or from the normed query rank where attention is latent
    indexer_heads: Optional[int] = None
    indexer_head_dim: int = 0
    topk: int = 0
    indexer_rope_dim: int = 0  # leading components of an index vector the rotary turns (0: all)
    indexer_key_norm: str = "rms"  # of the one index key: an RMS norm, or "layer" (gain and bias)
    # a selection WITHOUT an indexer (None: none): grouped-query attention restricted, a KEY HEAD, to the
    # blocks of keys its own queries score highest against the mean-pooled keys, in sequences longer
    # than its dense_len (`sparse_attention.select_blocks`; nothing is drawn for it)
    block_select: Optional[sa.BlockSelection] = None
    # the computation's tiles; no effect on the mathematics. Measured on the
    # v5e at 34,304 tokens: selection 36 ms a layer at 128 queries against 67
    # at 256 (512 needs 70 MB of VMEM for a tile's score row); attention under
    # the mask's sixteen key tiles of 2,176 (`sparse_attention.mask_tile`) 84.9
    # ms at 256 against 85.7 at 128; 512 rows of eight stacked heads do not fit
    # a score tile (my chip runs, PR 68; in 512-wide key tiles 124 / 146 / 128)
    q_tile: int = 128  # of the selection kernel (a query tile's whole score row sits in VMEM)
    kv_tile: int = 512  # sa_config's kv_chunk_size: the pieces the selection scores and counts in
    attn_q_tile: int = 256  # of the attention kernel, a multiple of q_tile
    # of the batched causal kernel. Without a selection q_tile and kv_tile do not bind it. On the
    # v5e at 4 x 8,704 tokens, heads of 64: 1088 x 1088 25.5 ms a layer, 512 x 1088 27.0, 256 x
    # 2176 27.3, 512 x 512 33.1, 256 x 512 40.5, 256 x 256 74.6 (my chip runs, PR 38). Under a
    # selection (latent attention) the key tile is the one `select_keys` WROTE its mask in, which
    # `sparse_attention.mask_tile` picks from S for this kernel (2,176 at 8,704 tokens and, over
    # keys padded to sixteen of them, at 34,304), and the query tile the largest multiple of q_tile
    # under causal_q_tile that divides S: 512 x 2,176 at 128 heads of 128 + 64, 34.8 ms a layer
    # where the 512 x 512 that kv_tile used to force took 43.4 (my chip runs, PR 47)
    causal_q_tile: int = 1088
    causal_kv_tile: int = 1088
    # rows a chunk of the delta rule's kernel (the largest whole-tile divisor of S under it): on
    # the v5e at 4 x 8,704 tokens 128 rows 7.5 ms a layer, 64 rows 10.1 (my chip runs, PR 56). (The
    # state-space scan's chunk is its own, from S alone: `ops/ssd.scan_rows`)
    linear_chunk: int = CHUNK
    # experts (num_experts 0: a dense gated MLP of intermediate_size)
    num_experts: int = 0
    experts_per_token: int = 0
    expert_width: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    norm_topk_prob: bool = True
    num_dense_layers: int = 0  # leading layers whose MLP is dense although there are experts
    router_scoring: str = "softmax"  # or "sigmoid" (moe.SCORINGS)
    expert_bias: bool = False  # experts CHOSEN by score + bias, weighted by the score alone
    gate_eps: float = 0.0  # in the renormalising sum of the chosen scores
    routed_scaling_factor: float = 1.0
    # group-limited routing (moe.route_top_k): the experts in router_groups runs, chosen among
    # the router_groups_kept best groups' only (1: no limit)
    router_groups: int = 1
    router_groups_kept: int = 1
    shared_experts: int = 0  # beside the routed ones, ungated: one MLP of shared_experts * expert_width
    intermediate_size: int = 0
    patch: int = 8

    @property
    def patch_dim(self) -> int:
        return self.patch * self.patch

    @property
    def rope_dim(self) -> int:
        """The width the rotary turns: the whole head, or a latent head's rotary part."""
        return self.qk_rope_head_dim if self.kv_lora_rank else self.rope_partial_dim or self.head_dim

    @property
    def holds_a_share(self) -> bool:
        """Of the routed experts: the step then counts :data:`SHARE_STATS` too."""
        return bool(self.num_experts) and self.experts_held[1] < self.num_experts

    @property
    def selects_over_latent(self) -> bool:
        """A key selection over latent attention: the step then counts
        :data:`SHARE_STATS` and :data:`PAIR_STATS` too."""
        return bool(self.indexer_heads) and bool(self.kv_lora_rank)

    @property
    def softmax_scale(self) -> float:
        """Of grouped-query attention's scores, before YaRN's ``mscale**2``."""
        return self.head_dim ** -0.5 if self.attention_multiplier is None else self.attention_multiplier

    @property
    def stream_dtype(self):
        """The type of the stream between the layers and of the embedded rows
        that start it: float32 where branches are added at a
        ``residual_multiplier``, else ``None`` (the weights' own, bf16). A
        branch then comes in at a fraction of its size, twice a layer: were
        the stream bf16, ITS rounding at every addition (and the rounding of
        the embedded rows times ``embedding_multiplier``) and not the
        products' would be the step's error (Granite-4.0-H's 40 layers read
        2.6-5.7 times what rounding every operand to bf16 costs; float32,
        1.0-1.5). Every product's operands stay the weights' type; the stream
        is 71 MB at 8,704 x 2,048, 1% of a layer's traffic. Under
        hyper-connections (``hc_mult``) the streams stay the weights' type: a
        doubly stochastic mix keeps their size and a branch comes in at order
        1 (``H_post`` in (0, 2)), so a rounding a branch is a plain bf16
        model's (PERF.md section 4 has the chip's reading), and a float32
        stream would be 1 GB, twice over, at 17,408 x 14,336."""
        return jnp.float32 if self.residual_multiplier != 1.0 else None

    @property
    def has_linear(self) -> bool:
        """Layers that carry a state (linear attention, or a state-space
        scan): the step then counts :data:`SHARE_STATS`, :data:`PAIR_STATS`
        and :data:`LINEAR_STATS` too."""
        return bool({LINEAR, MAMBA, MAMBA1} & set(self.layer_types))

    @property
    def cut_layer(self) -> Optional[int]:
        """The layer FROM which a step that serves some rows alone runs those
        rows alone (``trunk``'s ``rows``), ``None`` where the schedule has no
        such layer: the full ATTENTION layer whose keys and values the
        trailing layers read, where EVERY layer after it is a gated memory
        unit or cross attention — layers that mix no tokens: a row of theirs
        needs that row of the stream, that row of the scan output handed on,
        and the kept keys and values of ALL rows. The layer itself makes its
        keys and values on every row and runs its query side, its output and
        its feed-forward on the rows wanted (YOCO's prefill, arXiv:2405.05254:
        "early exit before the cross-decoder")."""
        tail = next((i for i in range(len(self.layer_types) - 1, -1, -1)
                     if self.layer_types[i] not in (GMU, CROSS)), -1)
        if tail < 0 or tail == len(self.layer_types) - 1 or self.layer_types[tail] != ATTENTION:
            return None
        return tail

    def read_from(self, i: int) -> Optional[int]:
        """The EARLIER layer whose product layer ``i`` reads: a gated memory
        unit the nearest MAMBA1 layer before it (its scan output), cross
        attention the nearest ATTENTION layer (its keys and values); ``None``
        for every other kind."""
        made_by = {GMU: MAMBA1, CROSS: ATTENTION}.get(self.layer_types[i] if self.layer_types else "")
        if made_by is None:
            return None
        return next((j for j in range(i - 1, -1, -1) if self.layer_types[j] == made_by), None)

    @property
    def hands_on(self) -> Tuple[int, ...]:
        """The layers whose product a later layer reads (:meth:`read_from`)."""
        return tuple(sorted({j for j in map(self.read_from, range(len(self.layer_types)))
                             if j is not None}))

    @property
    def has_window(self) -> bool:
        """Windowed layers: the step then counts :data:`SHARE_STATS` and
        :data:`PAIR_STATS` too (the band's pairs of what a causal layer has)."""
        return SLIDING in self.layer_types

    @property
    def counts_pairs(self) -> bool:
        """The step counts :data:`PAIR_STATS`: a selection's kept pairs (of
        keys over latent attention, of blocks a key head), or a band's."""
        return self.selects_over_latent or self.has_window or self.block_select is not None

    @property
    def rows_go_ahead(self) -> bool:
        """A share of the experts so large that ``dropless_moe`` takes a pass
        ahead of its held rows' loop: the step then counts every group, and
        :data:`AHEAD_STATS` after them."""
        return self.holds_a_share and goes_ahead(self.experts_held[1], self.num_experts)

    @property
    def layer_stats(self) -> int:
        """How many statistics a layer counts: the first four of
        :data:`STEP_STATS`, then the groups above."""
        if self.rows_go_ahead:
            return 4 + len(SHARE_STATS + PAIR_STATS + LINEAR_STATS + AHEAD_STATS)
        if self.has_linear:
            return 10
        if self.counts_pairs:
            return 8
        return 6 if self.holds_a_share else 4

    def heads(self, i: int) -> int:
        """Layer ``i``'s query heads."""
        return self.heads_per_layer[i] if self.heads_per_layer else self.num_heads

    def layer_kind(self, i: int) -> Tuple[Optional[str], Optional[bool]]:
        """``(operator, has experts)`` of layer ``i``: its operator, and
        whether its feed-forward is the experts (else the dense MLP). Where
        every layer is ONE block (``single_block``) one of the two is ``None``:
        ``(operator, None)`` is an operator with no feed-forward after it,
        ``(None, True)`` the experts alone, ``(None, False)`` a dense MLP alone."""
        op = self.layer_types[i] if self.layer_types else ATTENTION
        if op in (EXPERTS, DENSE):
            return None, op == EXPERTS
        if op == ATTENTION and self.kv_lora_rank:
            op = LATENT
        if self.single_block:
            return op, None
        return op, bool(self.num_experts) and i >= self.num_dense_layers

    @classmethod
    def from_mapping(cls, m: Mapping) -> "DecoderConfig":
        """From the keys of a Hugging Face ``config.json`` (as the
        benchmark's configuration file repeats them), plus ``patch``,
        ``experts_held`` and ``tie_embedding``. Thirteen spellings are read:
        Keye-VL-2.0's (``head_dim``, ``rms_norm_eps``,
        ``rope_scaling.mrope_section``, ``sa_config``); LFM2's
        (``norm_eps``, ``layer_types``, ``num_dense_layers``,
        ``conv_L_cache``, ``use_expert_bias`` with ``routed_scaling_factor``:
        sigmoid affinities, DeepSeek-V3's router; no ``head_dim``: hidden /
        heads; no ``rope_scaling``: plain rotary); and DeepSeek-V3's own, as
        Kimi-K2's file has it (``q_lora_rank``, ``kv_lora_rank``,
        ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``: latent
        attention without per-head norms; ``rope_scaling.type: yarn``;
        ``n_routed_experts``, ``n_shared_experts``,
        ``first_k_dense_replace``, ``scoring_func``, ``topk_method:
        noaux_tc``: the selection bias; ``n_group`` and ``topk_group``: the
        group limit; and as DeepSeek-V3.2's file has it, ``index_n_heads``,
        ``index_head_dim``, ``index_topk``: the key selection over latent
        attention, whose index key goes through a LayerNorm and whose
        rotary turns ``qk_rope_head_dim`` of an index vector); and
        Ling-3.0's (``bailing_hybrid``: DeepSeek-V3's keys with
        ``num_experts`` and ``num_shared_experts``; ``q_lora_rank`` null
        beside a ``kv_lora_rank``: a full-rank query;
        ``gated_attention_proj_granularity_type``: the output gate;
        ``layer_types`` entries ``linear_attention`` with ``head_dim``,
        ``short_conv_kernel_size`` and ``kda_lower_bound``: the delta
        rule's layers); and Laguna's (``laguna``: ``layer_types`` entries
        ``sliding_attention`` under a ``sliding_window``;
        ``num_attention_heads_per_layer``; ``rope_parameters``, a rotary by
        layer type, the full layers' YaRN over ``partial_rotary_factor`` of
        a head with ``attention_factor`` on its cosines and sines, the
        sliding layers' plain over the whole head; ``gating: per-head``:
        the output gate, on grouped-query attention; no per-head q / k
        norm; ``mlp_only_layers``, ``shared_expert_intermediate_size`` and
        ``moe_routed_scaling_factor``; the router's score, which its
        config.json does not name, under the file's own ``router_scoring``);
        and Granite-4.0-H's (``granitemoehybrid``), each key read for
        itself whatever the file's ``model_type``: ``layer_types`` entries
        ``mamba`` and ``attention``, the second read as ``full_attention``;
        ``mamba_n_heads`` with ``mamba_d_head``, ``mamba_d_state``,
        ``mamba_d_conv``, ``mamba_conv_bias``: the state-space layers, one
        group; ``residual_multiplier``, ``embedding_multiplier``,
        ``logits_scaling`` (1 where a file has none) and
        ``attention_multiplier`` (the softmax scale, and no per-head q / k
        norm); ``position_embedding_type: nope``: no rotary;
        ``shared_intermediate_size``: the always-on MLP, which with
        ``num_local_experts`` 0 is the whole feed-forward); and Ouro's
        (``ouro``: ``total_ut_steps``, the passes through the one stack,
        which also says that a layer's branches are normed twice, that an
        exit gate reads every pass's normed rows and that q and k have no
        norm, none of which the file has a key for;
        ``early_exit_threshold``, held to 1); and Nemotron-H's
        (``nemotron_h``: ``hybrid_override_pattern``, a letter a layer, each
        layer ONE block: ``M`` a state-space layer, ``*`` grouped-query
        attention, ``E`` the experts, ``-`` a dense MLP, its first
        ``num_hidden_layers`` letters read; ``mamba_num_heads`` with
        ``mamba_head_dim``, ``ssm_state_size``, ``conv_kernel``,
        ``use_conv_bias`` and ``n_groups``, the scan's groups of B and C
        (beside ``n_group``, the ROUTER's groups: one letter apart);
        ``layer_norm_epsilon``; ``mlp_hidden_act: relu2``: experts, shared
        expert and dense MLP UNGATED, ``relu(x W_up)^2 W_down``;
        ``moe_shared_expert_intermediate_size``, the shared expert's whole
        width; and, as its module has them and its file has no key for,
        DeepSeek-V3's router (sigmoid affinities, a selection bias, 1e-20), no
        rotary and no per-head norm in an attention layer); and
        Olmo-Hybrid's (``olmo_hybrid``: ``layer_types`` entries
        ``linear_attention`` beside ``linear_key_head_dim``,
        ``linear_value_head_dim``, ``linear_num_key_heads`` =
        ``linear_num_value_heads``, ``linear_conv_kernel_dim`` and
        ``linear_allow_neg_eigval``: Gated DeltaNet's layers, ONE decay a head
        over a rectangular state, the step size in (0, 2) where eigenvalues
        may be negative, a SiLU gate after the output's norm;
        ``rope_parameters`` with ONE ``rope_theta``, null: no rotary; and, as
        its family has them and its file has no key for, OLMo 2's reordered
        norm — a branch normed AFTER it is computed and nothing before it — on
        both kinds of layer, and an RMS norm over the WHOLE query and key
        projections before the heads are cut: the ``linear_*`` keys ARE read
        as that family's mark, as ``total_ut_steps`` is the looped stack's,
        so a file with them whose three head counts differ or whose
        ``rope_theta`` is not null — another model that spells its linear
        layers so — is refused and never given this block); and
        MiniCPM-SALA's (``minicpm_sala``: ``mixer_types``, a word a layer,
        ``minicpm4`` grouped-query attention under a selection of BLOCKS a
        key head with no indexer (its sizes the file's ``sparse_config`` or,
        where it has none, MiniCPM4's published ones), ``lightning-attn``
        linear attention with a FIXED decay a head (``lightning_nh`` =
        ``lightning_nkv`` = the attention's heads, ``lightning_head_dim``,
        ``lightning_use_rope``, with ``qk_norm``, ``use_output_norm`` and
        ``use_output_gate`` all true: a norm a head on q and k, on the
        LINEAR layers alone, the output normed a head and then gated);
        ``attn_use_rope``; ``attn_use_output_gate``: an elementwise sigmoid
        gate; MiniCPM's three multipliers, ``scale_emb`` on the embedded
        rows, ``scale_depth`` over the root of the PUBLISHED depth on every
        branch (``published.num_hidden_layers`` where the file is a cut) and
        ``hidden_size / dim_model_base`` under the logits; a word it does not
        know, or a count that is not the layers', is refused); and
        Phi-4-mini-flash's (``phi4flash``: ``mb_per_layer``, the mark of
        SambaY's decoder-hybrid-decoder, whose schedule is the published RULE
        over ``num_hidden_layers``: a Mamba-1 layer at every even index up to
        ``L/2``, differential attention under ``sliding_window`` at every odd
        one below it, ONE full differential layer at ``L/2 + 1`` whose keys
        and values are kept, then gated memory units (even) and cross
        attention (odd) in turn; refused where ``num_hidden_layers % 4`` is
        not 0, as the published code asserts, or ``mb_per_layer`` is not 2;
        ``layer_norm_eps``: LayerNorm with a bias before every branch and
        before the head; no ``rope_theta``: no rotary; the scan's four sizes
        the configuration class's defaults — a state of 16, 4 taps, channels
        twice the hidden size, a rank of ``ceil(hidden / 16)`` — or the
        mapping's ``mamba_d_state``, ``mamba_d_conv``, ``mamba_expand``,
        ``mamba_dt_rank``; and, as its module has them and its file has no
        key for, differential attention, a bias on the attention's products
        and on the convolution, none in the MLP or the head); and
        Xing4.0's (``xing4_0``: DeepSeek-V3's keys beside ``hc_mult``,
        ``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min`` / ``_max``:
        the residual path is ``hc_mult`` streams under manifold-constrained
        hyper-connections; refused without the latent keys, beside an indexer
        or an output gate, or at a count of streams outside 2 to 8). Where a
        file's ``n_routed_experts`` counts the experts HELD (a chip's
        share), ``router_experts`` gives the width the router keeps."""
        sa_cfg = m.get("sa_config")
        index = {}
        if sa_cfg:
            index = dict(indexer_heads=int(sa_cfg["indexer_num_heads"]),
                         indexer_head_dim=int(sa_cfg["indexer_head_dim"]),
                         topk=int(sa_cfg["topk"]), kv_tile=int(sa_cfg["kv_chunk_size"]))
        elif m.get("index_n_heads"):
            index = dict(indexer_heads=int(m["index_n_heads"]),
                         indexer_head_dim=int(m["index_head_dim"]), topk=int(m["index_topk"]),
                         indexer_rope_dim=int(m.get("qk_rope_head_dim", 0)),
                         indexer_key_norm="layer")
        held_key = "num_experts" if "num_experts" in m else "n_routed_experts"
        n_exp = int(m.get("router_experts", m.get(held_key, 0)))
        n_layers, heads = int(m["num_hidden_layers"]), int(m["num_attention_heads"])
        # Granite-4.0-H's spelling of a full causal layer is "attention"
        layer_types = tuple(ATTENTION if op == "attention" else op
                            for op in m.get("layer_types", ()))
        pattern = m.get("hybrid_override_pattern")  # Nemotron-H's: a letter a layer, ONE block each
        if pattern is not None:
            if len(pattern) < n_layers or set(pattern) - set(PATTERN):
                raise ValueError(f"hybrid_override_pattern {pattern!r} does not spell {n_layers} "
                                 f"layers' blocks, each one of {''.join(PATTERN)}")
            layer_types = tuple(PATTERN[letter] for letter in pattern[:n_layers])
        mixers = m.get("mixer_types")  # MiniCPM-SALA's: a word a layer
        mixed = {}  # what that spelling sets beside the schedule
        if mixers is not None:
            if len(mixers) != n_layers or set(mixers) - set(MIXERS):
                raise ValueError(f"mixer_types {list(mixers)} does not spell {n_layers} layers' "
                                 f"operators, each one of {sorted(MIXERS)}")
            layer_types = tuple(MIXERS[word] for word in mixers)
            if (int(m["lightning_nh"]) != heads or int(m["lightning_nkv"]) != heads
                    or m.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)"
                    or not (m.get("qk_norm") and m.get("use_output_norm") and m.get("use_output_gate"))):
                raise ValueError("linear layers whose heads are not the attention's, whose scale is "
                                 "not 1/sqrt(d) or that lack the q / k norm, the output's norm or "
                                 "its gate are not built")
            depth = int((m.get("published") or {}).get("num_hidden_layers", n_layers))
            mixed = dict(residual_multiplier=float(m.get("scale_depth", 1.0)) / depth ** 0.5,
                         embedding_multiplier=float(m.get("scale_emb", 1.0)),
                         logits_scaling=int(m["hidden_size"]) / float(m.get("dim_model_base",
                                                                            m["hidden_size"])),
                         block_select=sa.BlockSelection(**{k: int(v) for k, v in (
                             m.get("sparse_config") or {}).items()}),
                         linear_decay="fixed", linear_rotary=bool(m.get("lightning_use_rope")),
                         linear_chunk=lightning.CHUNK)
        flash = {}  # SambaY's decoder-hybrid-decoder (phi4flash), by the key that spaces its scans
        if "mb_per_layer" in m:
            if int(m["mb_per_layer"]) != 2 or n_layers % 4 or n_layers < 8:
                raise ValueError(f"mb_per_layer {m['mb_per_layer']} over {n_layers} layers is not "
                                 "built: a scan every 2 layers over a multiple of 4 layers is (the "
                                 "published code asserts num_hidden_layers % 4 == 0)")
            if m.get("mlp_bias") or m.get("lm_head_bias") or not m.get("sliding_window"):
                raise ValueError("a bias in the MLP or the head, or no sliding_window, is not built")
            half = n_layers // 2

            def published(i):  # the published rule over the layer's index
                if i % 2 == 0:
                    return MAMBA1 if i <= half else GMU
                return SLIDING if i < half else ATTENTION if i == half + 1 else CROSS

            layer_types = tuple(published(i) for i in range(n_layers))
            width = int(m["hidden_size"])
            # the scan's four sizes are the configuration CLASS's defaults (the file has no key for
            # them): a state of 16, 4 taps, channels twice the hidden size, a rank of ceil(D / 16)
            flash = dict(scan_channels=int(m.get("mamba_expand", 2)) * width,
                         scan_dt_rank=int(m.get("mamba_dt_rank") or -(-width // 16)),
                         ssm_state=int(m.get("mamba_d_state", 16)), conv_bias=True,
                         norm="layer", attn_bias=True, diff_attention=True)
            if 2 * flash["ssm_state"] > SCAN_LANES or int(m["num_key_value_heads"]) % 2 or (
                    heads // 2) % (int(m["num_key_value_heads"]) // 2):
                raise ValueError("a scan's state over 64, or heads that do not pair, are not built")
        ssm = {}
        if "mamba_n_heads" in m:  # Mamba-2's state-space layers, as Granite-4.0-H spells them
            ssm = dict(ssm_heads=int(m["mamba_n_heads"]), ssm_head_dim=int(m["mamba_d_head"]),
                       ssm_state=int(m["mamba_d_state"]), ssm_groups=int(m.get("mamba_n_groups", 1)),
                       conv_bias=bool(m.get("mamba_conv_bias", False)))
        elif "mamba_num_heads" in m:  # and as Nemotron-H does (n_groups: the scan's, not n_group)
            ssm = dict(ssm_heads=int(m["mamba_num_heads"]), ssm_head_dim=int(m["mamba_head_dim"]),
                       ssm_state=int(m["ssm_state_size"]), ssm_groups=int(m.get("n_groups", 1)),
                       conv_bias=bool(m.get("use_conv_bias", False)))
        if ssm and ssm["ssm_heads"] % ssm["ssm_groups"]:
            raise ValueError(f"{ssm['ssm_groups']} groups of B and C do not divide "
                             f"{ssm['ssm_heads']} heads")
        single = pattern is not None  # a schedule of single blocks: what its module does, its file has no key for
        act = m.get("mlp_hidden_act", "silu")
        if act not in ("silu", "relu2"):
            raise ValueError(f"mlp_hidden_act {act!r} is not built (silu, gated; relu2, ungated)")
        if "shared_intermediate_size" in m and int(m.get("num_local_experts") or 0):
            raise ValueError("experts (num_local_experts) beside the always-on MLP of "
                             "shared_intermediate_size are not built")
        # no file has a key for the per-head norm of q and k: the files that state their softmax
        # scale (Granite's) or a rotary a layer type (Laguna's) are of models without one
        scale = m.get("attention_multiplier")
        loop = {}
        if "total_ut_steps" in m:  # Ouro's looped stack: sandwich norms, an exit gate, no q / k norm
            if float(m.get("early_exit_threshold", 1)) != 1:
                raise ValueError(
                    f"early_exit_threshold {m['early_exit_threshold']} is not 1: an exit before the "
                    f"last pass (a pass count the data sets) is not supported")
            loop = dict(passes=int(m["total_ut_steps"]), sandwich=True, exit_gate=True)
        # Gated DeltaNet's layers, by the keys that size them (Olmo-Hybrid's spelling)
        delta_net = "linear_key_head_dim" in m
        if delta_net and (int(m["linear_num_key_heads"]) != int(m["linear_num_value_heads"])
                          or int(m["linear_num_key_heads"]) != heads):
            raise ValueError(f"linear layers of {m['linear_num_key_heads']} key heads, "
                             f"{m['linear_num_value_heads']} value heads beside {heads} attention "
                             "heads are not built: one count for all three is")
        # what those layers are, and what that family does beside them: a branch normed AFTER it and
        # nothing before it, q and k normed over their whole projections. None of it is a key of the
        # file: the linear_* keys ARE read as the family's mark, so a file that has them and is not
        # of that family's shape (one head count, no rotary: below) is refused, never built as this
        net = dict(linear_value_dim=int(m["linear_value_head_dim"]), linear_decay="head",
                   linear_beta_scale=2.0 if m.get("linear_allow_neg_eigval") else 1.0,
                   linear_chunk=HEAD_CHUNK, sandwich=True, pre_norm=False,
                   qk_norm_span="projection") if delta_net else {}
        qk_norm = delta_net or (scale is None and "rope_parameters" not in m and not loop
                                and not single and mixers is None  # (mixers: the LINEAR layers' alone)
                                and not flash)
        if layer_types and (len(layer_types) != n_layers
                            or set(layer_types) - {ATTENTION, SLIDING, CONV, LINEAR}
                            - ({MAMBA} if ssm else set())
                            - ({MAMBA1, GMU, CROSS} if flash else set())
                            - ({EXPERTS, DENSE} if single else set())):
            raise ValueError(f"layer_types {layer_types} does not name {n_layers} layers' "
                             f"operators, each {ATTENTION!r}, {SLIDING!r}, {CONV!r} or {LINEAR!r}")
        latent = int(m.get("kv_lora_rank") or 0)
        if latent and m.get("position_embedding_type") == "nope":
            raise ValueError("latent attention without a rotary is not built")
        hyper = {}
        if "hc_mult" in m:  # xing4_0's: DeepSeek-V3's block under manifold-constrained hyper-connections
            if not latent or index or m.get("gated_attention_proj_granularity_type"):
                raise ValueError("hc_mult without the latent-attention keys (kv_lora_rank and the head's "
                                 "parts), or beside an indexer or an output gate, is not built: the "
                                 "streams' mixes stand around DeepSeek-V3's two branches alone")
            if not 2 <= int(m["hc_mult"]) <= hc.GROUP:
                # ONE stream is no plain residual either: its branch would read sigmoid(h) x and be
                # added at 2 sigmoid(h), a model no file describes
                raise ValueError(f"hc_mult {m['hc_mult']} is not built: 2 to {hc.GROUP} streams are")
            hyper = dict(hc_mult=int(m["hc_mult"]), hc_iters=int(m["hc_sinkhorn_iters"]),
                         hc_eps=float(m["hc_eps"]),
                         hc_clamp=(float(m.get("mhc_h_res_clamp_min", -30.0)),
                                   float(m.get("mhc_h_res_clamp_max", 30.0))))
        head_dim = int(m.get("head_dim") or int(m["hidden_size"]) // heads)
        by_type = m.get("rope_parameters")  # Laguna's: a rotary a layer type
        flat = None
        if by_type is not None and ATTENTION not in by_type:  # Olmo-Hybrid's: ONE rotary's own
            flat, by_type = by_type, None
        if delta_net and (flat is None or flat.get("rope_theta") is not None):
            raise ValueError("linear_* keys beside a rotary (rope_parameters.rope_theta is not null) "
                             "are not built: those keys are read as the mark of a position-free "
                             "block under the reordered norm, and no key of the file says otherwise")
        window = {}
        if by_type:
            rope = dict(by_type[ATTENTION])
            rope["type"] = rope.get("rope_type")
            theta = float(rope["rope_theta"])
            turned = round(head_dim * float(rope.get("partial_rotary_factor", 1)))
            sliding = by_type.get(SLIDING) or {}
            if (sliding.get("rope_type", "default") != "default"
                    or float(sliding.get("partial_rotary_factor", 1)) != 1):
                raise ValueError(f"a sliding layer's rotary {sliding} is not the plain one over "
                                 f"the whole head: not built")
            window = dict(sliding_window=int(m.get("sliding_window") or 0),
                          rope_partial_dim=0 if turned == head_dim else turned,
                          sliding_rope_theta=float(sliding.get("rope_theta", theta)),
                          heads_per_layer=tuple(
                              int(h) for h in m.get("num_attention_heads_per_layer", ())))
            if len(window["heads_per_layer"]) not in (0, n_layers):
                raise ValueError(f"num_attention_heads_per_layer names {len(window['heads_per_layer'])} "
                                 f"layers' heads, not {n_layers}")
            if m.get("moe_router_logit_softcapping"):
                raise ValueError("a soft cap on the router's logits is not built")
        elif flat is not None:  # a null theta: q and k are not turned at all
            rope, theta = {}, float(flat.get("rope_theta") or 0.0)
        elif flash:  # no rotary and no other position signal anywhere: the file has no rope_theta
            rope, theta = {}, 0.0
            window = dict(sliding_window=int(m["sliding_window"]))
        else:
            rope, theta = m.get("rope_scaling") or {}, float(m["rope_theta"])
        if SLIDING in layer_types and not window.get("sliding_window"):
            raise ValueError(f"{SLIDING!r} layers and no sliding_window under rope_parameters")
        mrope = rope.get("mrope_section")
        yarn = None
        if rope.get("type") == "yarn":
            yarn = Yarn(float(rope["factor"]), int(rope["original_max_position_embeddings"]),
                        float(rope.get("beta_fast", 32)), float(rope.get("beta_slow", 1)),
                        float(rope.get("mscale", 1)), float(rope.get("mscale_all_dim", 0)))
            stated = rope.get("attention_factor")  # what the cosines and sines are multiplied by
            if stated is not None and abs(float(stated) - yarn.rotary_scale) > 1e-6:
                raise ValueError(f"attention_factor {stated} is not YaRN's own "
                                 f"{yarn.rotary_scale}: not built")
        if latent:
            gate = str(m.get("gated_attention_proj_granularity_type") or "")
        else:  # Laguna's spelling, on grouped-query attention
            gate = str(m.get("gating") or "")
            gate = "head_wise" if gate == "per-head" else gate
        if mixers is not None and m.get("attn_use_output_gate"):
            gate = "elementwise"
        if gate not in ("", "head_wise", "elementwise"):
            raise ValueError(f"an output gate of granularity {gate!r} is not built")
        dense_only = sorted(int(i) for i in m.get("mlp_only_layers", ()))
        if dense_only != list(range(len(dense_only))):
            raise ValueError(f"mlp_only_layers {dense_only} are not the leading layers: not built")
        linear = LINEAR in layer_types
        nope, rope_dim = int(m.get("qk_nope_head_dim", 0)), int(m.get("qk_rope_head_dim", 0))
        # DeepSeek-V3's router, by its spelling or (Laguna's file) by the reading stated there
        # (or, Nemotron-H's file having no key for its router, by its schedule's spelling)
        deepseek = "scoring_func" in m or "router_scoring" in m or single
        sigmoid = ("use_expert_bias" in m or m.get("scoring_func") == "sigmoid"
                   or m.get("router_scoring") == "sigmoid" or single)
        width = int(m.get("moe_intermediate_size", 0))
        return cls(
            hidden_size=int(m["hidden_size"]), num_layers=n_layers,
            num_heads=heads, num_kv_heads=int(m["num_key_value_heads"]),
            head_dim=nope + rope_dim if latent else head_dim,
            vocab_size=int(m["vocab_size"]),
            rms_eps=float(m["rms_norm_eps"] if "rms_norm_eps" in m
                          else m["layer_norm_epsilon"] if "layer_norm_epsilon" in m
                          else m["layer_norm_eps"] if "layer_norm_eps" in m else m["norm_eps"]),
            rope_theta=theta,
            mrope_section=tuple(int(v) for v in mrope) if mrope else None,
            layer_types=layer_types, **window, **{**ssm, **flash}, qk_norm=qk_norm,
            single_block=single, mlp_act=act,
            **{"residual_multiplier": float(m.get("residual_multiplier", 1.0)),
               "embedding_multiplier": float(m.get("embedding_multiplier", 1.0)),
               "logits_scaling": float(m.get("logits_scaling", 1.0)), **mixed},
            attention_multiplier=None if scale is None else float(scale),
            rotary=(m.get("position_embedding_type") != "nope" and not single and not flash
                    and (flat is None or flat.get("rope_theta") is not None)
                    and bool(m.get("attn_use_rope", True))),
            conv_taps=int(m.get("conv_L_cache", m.get("short_conv_kernel_size", m.get(
                "mamba_d_conv", m.get("conv_kernel", m.get("linear_conv_kernel_dim",
                                                           4 if flash else 3)))))),
            linear_head_dim=int(m["linear_key_head_dim" if delta_net else "lightning_head_dim"
                                  if mixers is not None else "head_dim"]) if linear else 0,
            linear_decay_floor=(float(m["kda_lower_bound"])
                                if linear and not delta_net and mixers is None else 0.0),
            **net,
            tie_embedding=bool(m.get("tie_embedding", m.get("tie_word_embeddings", False))),
            **loop, **hyper,
            rope_yarn=yarn,
            q_lora_rank=int(m.get("q_lora_rank") or 0) if latent else 0, kv_lora_rank=latent,
            qk_nope_head_dim=nope if latent else 0, qk_rope_head_dim=rope_dim if latent else 0,
            v_head_dim=int(m.get("v_head_dim", 0)) if latent else 0, attn_gate=gate,
            **index,
            num_experts=n_exp, experts_per_token=int(m.get("num_experts_per_tok", 0)),
            expert_width=width,
            experts_held=tuple(int(v) for v in m.get("experts_held", (0, n_exp))),
            norm_topk_prob=bool(m.get("norm_topk_prob", True)),
            num_dense_layers=int(m.get("num_dense_layers",
                                       m.get("first_k_dense_replace", len(dense_only)))),
            router_scoring="sigmoid" if sigmoid else "softmax",
            expert_bias=bool(m.get("use_expert_bias", m.get("topk_method") == "noaux_tc" or single)),
            # the renormalising sum's epsilon: LFM2's code has 1e-6, DeepSeek-V3's 1e-20
            gate_eps=(1e-20 if deepseek else 1e-6) if sigmoid else 0.0,
            routed_scaling_factor=float(m.get("routed_scaling_factor",
                                              m.get("moe_routed_scaling_factor", 1.0))),
            router_groups=int(m.get("n_group") or 1),
            router_groups_kept=int(m.get("topk_group") or 1),
            # (a file that states the shared expert's whole width is read by that: Nemotron-H's
            # one shared expert is 3,712 wide beside routed ones of 1,856)
            shared_experts=int(int(m.get("moe_shared_expert_intermediate_size", 0)) // max(width, 1)
                               or m.get("n_shared_experts") or m.get("num_shared_experts")
                               or int(m.get("shared_expert_intermediate_size", 0)) // max(width, 1)
                               ) if n_exp else 0,
            intermediate_size=int(m.get("shared_intermediate_size", m.get("intermediate_size", 0))),
            patch=int(m.get("patch", 8)),
        )


# ---------------------------------------------------------------------------
# parameters: bf16, made on the device from a key
# ---------------------------------------------------------------------------

def init_params(cfg: DecoderConfig, key, dtype=jnp.bfloat16) -> dict:
    """normal(0, 0.02) matrices (the router's selection bias too, in
    float32) and unit gains, as one tree: ``{"patch", "embed", "layers":
    [..], "norm", "head"}`` (no ``head`` where it is the embedding; an
    ``exit_gate`` ``{"w" [D], "b"}`` where the model has one). Call
    under ``jax.jit`` to make the weights on the device."""
    d, hd = cfg.hidden_size, cfg.head_dim
    # a latent layer with an indexer draws 17 matrices: 20 keys a layer there, and where the
    # count was 16 it stays 16 (the same seed, the same weights)
    keys = iter(jax.random.split(
        key, (24 if cfg.hc_mult else 20 if cfg.selects_over_latent else 16) * cfg.num_layers + 8))

    def w(*shape, dtype=dtype, scale=0.02):
        return (scale * jax.random.normal(next(keys), shape, jnp.float32)).astype(dtype)

    def between(low, high, *shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, low, high)

    def gain(n):
        return jnp.ones((n,), dtype)

    layers = []
    for i in range(cfg.num_layers):
        op, experts = cfg.layer_kind(i)
        if op is None:  # a feed-forward alone: the block's one norm is norm2, below
            p = {}
        elif op == CONV:
            p = {"norm1": gain(d), "w_in": w(d, 3 * d), "conv_w": w(d, cfg.conv_taps),
                 "w_out": w(d, d)}
        elif op == LINEAR and cfg.linear_decay == "head":
            wide, values = cfg.num_heads * cfg.linear_head_dim, cfg.num_heads * cfg.linear_value_dim
            # W_q, W_k, W_v and each one's taps, the published shapes; taps of order 1 (as
            # above); Mamba-2's published initialiser for the gate (A = U(1, 16); the bias the inverse
            # softplus of a log-uniform step in [0.001, 0.1]: log-decays of -0.001 to -1.6 a token where
            # the pre-activation is 0), and W_a at 1/4 over sqrt(D): the pre-activation of a stream of
            # RMS r is normal(0, r / 4), so alpha spreads over (e^-3, 1) and past it in the tails, and
            # a decay stuck at either end would show
            first = jnp.exp(between(np.log(0.001), np.log(0.1), cfg.num_heads))
            p = {"norm1": gain(d), "w_q": w(d, wide), "w_k": w(d, wide), "w_v": w(d, values),
                 **{name: w(width, cfg.conv_taps, scale=0.5)
                    for name, width in (("conv_q", wide), ("conv_k", wide), ("conv_v", values))},
                 "w_f": w(d, cfg.num_heads, scale=0.25 * d ** -0.5),
                 "dt_bias": first + jnp.log(-jnp.expm1(-first)),
                 "a_log": jnp.log(between(1.0, 16.0, cfg.num_heads)),
                 "w_beta": w(d, cfg.num_heads), "w_z": w(d, values),
                 "o_norm": gain(cfg.linear_value_dim), "wo": w(values, d)}
        elif op == LINEAR and cfg.linear_decay == "fixed":
            wide, dl = cfg.num_heads * cfg.linear_head_dim, cfg.linear_head_dim
            # no decay, no step size, no taps: five matrices and three gains of a head's width (q and
            # k leave their norms at RMS 1 a column: a score's standard deviation is 1 under d ** -0.5)
            p = {"norm1": gain(d), "w_q": w(d, wide), "w_k": w(d, wide), "w_v": w(d, wide),
                 "q_norm": gain(dl), "k_norm": gain(dl), "w_z": w(d, wide), "o_norm": gain(dl),
                 "wo": w(wide, d)}
        elif op == LINEAR:
            wide = cfg.num_heads * cfg.linear_head_dim
            # taps of order 1, so that the SiLU is not in its linear part; the decay's A and b
            # spread it over its range: a channel's mean log-decay from -0.01 to -4.4 a token
            p = {"norm1": gain(d), "w_qkv": w(d, 3 * wide),
                 "conv_w": w(3 * wide, cfg.conv_taps, scale=0.5), "w_f": w(d, wide),
                 "decay_a": between(-0.7, 0.7, cfg.num_heads), "decay_b": between(-6.0, 2.0, wide),
                 "w_beta": w(d, cfg.num_heads), "w_z": w(d, wide),
                 "o_norm": gain(cfg.linear_head_dim), "wo": w(wide, d)}
        elif op == MAMBA:
            wide = cfg.ssm_heads * cfg.ssm_head_dim
            # [x | B | C]: what the convolution passes over, a B and a C a group of heads
            conv = wide + 2 * cfg.ssm_groups * cfg.ssm_state
            # Mamba-2's published initialiser: A = -U(1, 16), the step's bias the inverse softplus
            # of a log-uniform step in [0.001, 0.1] (log-decays from -0.001 to -1.6 a token), D 1;
            # taps of order 1 (ling3's) and a bias in +-0.5, so that the SiLU is not in its linear part
            first = jnp.exp(between(np.log(0.001), np.log(0.1), cfg.ssm_heads))
            p = {"norm1": gain(d), "w_in": w(d, wide + conv + cfg.ssm_heads),
                 "conv_w": w(conv, cfg.conv_taps, scale=0.5),
                 "dt_bias": first + jnp.log(-jnp.expm1(-first)),
                 "a_log": jnp.log(between(1.0, 16.0, cfg.ssm_heads)),
                 "d_skip": jnp.ones((cfg.ssm_heads,), jnp.float32), "ssm_norm": gain(wide),
                 "w_out": w(wide, d)}
            if cfg.conv_bias:
                p["conv_b"] = between(-0.5, 0.5, conv).astype(dtype)
        elif op == MAMBA1:
            wide, rank, n = cfg.scan_channels, cfg.scan_dt_rank, cfg.ssm_state
            # Mamba-1's published initialiser: A = -(1 .. N) in every channel (an OPERAND of the
            # kernel all the same: a trained A is whatever training left), W_dt uniform within
            # rank ** -0.5, the step's bias the inverse softplus of a log-uniform step in [0.001,
            # 0.1], D 1; taps and their bias as the other state-space kind's, above
            first = jnp.exp(between(np.log(0.001), np.log(0.1), wide))
            p = {"norm1": gain(d), "w_in": w(d, 2 * wide), "conv_w": w(wide, cfg.conv_taps, scale=0.5),
                 "conv_b": between(-0.5, 0.5, wide).astype(dtype), "w_x": w(wide, rank + 2 * n),
                 "w_dt": between(-rank ** -0.5, rank ** -0.5, rank, wide).astype(dtype),
                 "dt_bias": first + jnp.log(-jnp.expm1(-first)),
                 "a_log": jnp.log(jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (wide, n))),
                 "d_skip": jnp.ones((wide,), jnp.float32), "w_out": w(wide, d)}
        elif op == GMU:  # silu(a W_1) times the scan output handed on, then W_2
            p = {"norm1": gain(d), "w_1": w(d, cfg.scan_channels), "w_2": w(cfg.scan_channels, d)}
        elif cfg.diff_attention and op in (ATTENTION, SLIDING, CROSS):
            # W_qkv as published, [q | k | v] with a bias (cross attention: W_q alone); four lambda
            # vectors normal(0, 0.1), the sub-norm's gain over a pair's 2 * head_dim columns
            wide = cfg.num_heads * hd + (0 if op == CROSS else 2 * cfg.num_kv_heads * hd)
            p = {"norm1": gain(d), "w_q" if op == CROSS else "w_qkv": w(d, wide),
                 **{name: w(hd, dtype=jnp.float32, scale=0.1)
                    for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")},
                 "sub_norm": gain(2 * hd), "wo": w(cfg.num_heads * hd, d)}
            if cfg.attn_bias:
                p.update({"b_q" if op == CROSS else "b_qkv": w(wide), "b_o": w(d)})
        elif op == LATENT:
            heads, rq, rkv = cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank
            if rq:
                p = {"norm1": gain(d), "wq_a": w(d, rq), "q_a_norm": gain(rq),
                     "wq_b": w(rq, heads * hd)}
            else:
                p = {"norm1": gain(d), "wq": w(d, heads * hd)}
            p.update({"wkv_a": w(d, rkv + cfg.qk_rope_head_dim), "kv_a_norm": gain(rkv),
                      "wkv_b": w(rkv, heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                      "wo": w(heads * cfg.v_head_dim, d)})
            if cfg.attn_gate:
                p["w_attn_gate"] = w(d, heads)
            if cfg.indexer_heads:  # the index queries read the query's normed low rank
                p.update(_index_params(cfg, w, gain, rq))
        else:  # grouped-query attention, full or windowed, this layer's own count of query heads
            heads = cfg.heads(i)
            # under a STATED softmax scale W_q and W_k are drawn so that the scores spread as they
            # do under head_dim ** -0.5 (the factor is 1 there): at Granite's 1 / 64, normal(0,
            # 0.02) leaves scores of a tenth and the softmax a mean over the causal keys, whatever
            # scales or turns q and k (a trained model's scores are sharp: that is what the scale
            # was trained with)
            sharp = 0.02 * (hd ** -0.5 / cfg.softmax_scale) ** 0.5
            p = {
                "norm1": gain(d), "wq": w(d, heads * hd, scale=sharp),
                "wk": w(d, cfg.num_kv_heads * hd, scale=sharp),
                "wv": w(d, cfg.num_kv_heads * hd), "wo": w(heads * hd, d),
            }
            if cfg.qk_norm:  # a gain a head's component, or one a column of the whole projection
                whole = cfg.qk_norm_span == "projection"
                p.update(q_norm=gain(heads * hd if whole else hd),
                         k_norm=gain(cfg.num_kv_heads * hd if whole else hd))
            if cfg.attn_gate:  # a scalar a head, or one a column of the output
                p["w_attn_gate"] = w(d, heads * hd if cfg.attn_gate == "elementwise" else heads)
            if cfg.indexer_heads:
                p.update(_index_params(cfg, w, gain, d))
        if cfg.hc_mult:
            # a branch's phi [n D, n (n + 2)] = [pre | post | res], its three scalars and its bias. The
            # publication starts alpha small (every H its bias); here alpha is of order 1/2 and the
            # biases normal(0, 1), so that the products x~ phi (a deviation of 0.02 sqrt(n D)) MOVE the
            # mixing numbers and a fault in them shows (a trained model's are whatever training left)
            n = cfg.hc_mult
            for which in ("hc1", "hc2"):  # the operator's, the feed-forward's
                p.update({which + "_phi": w(n * d, n * (n + 2)),
                          which + "_alpha": between(0.25, 0.75, 3),
                          which + "_b": w(n * (n + 2), dtype=jnp.float32, scale=1.0)})
        if not cfg.pre_norm:  # nothing norms a branch's input: only what a block has is drawn
            p.pop("norm1", None)
        if cfg.sandwich:  # each branch's second norm
            p.update(norm1_post=gain(d), norm2_post=gain(d))
        gated = cfg.mlp_act == "silu"  # an ungated MLP (relu2) has no W_gate: only what a block has is drawn
        if experts is not None and cfg.pre_norm:
            p["norm2"] = gain(d)  # the feed-forward's norm (an operator ALONE has none)
        if cfg.norm == "layer":  # a LayerNorm's bias beside every gain of a block's
            p.update({f"{name}_b": w(d) for name in ("norm1", "norm2") if name in p})
        if experts:
            held = cfg.experts_held[1]
            p.update(router=w(d, cfg.num_experts))
            if gated:
                p.update(w_gate=w(held, d, cfg.expert_width))
            p.update(w_up=w(held, d, cfg.expert_width), w_down=w(held, cfg.expert_width, d))
            if cfg.expert_bias:
                p.update(router_bias=w(cfg.num_experts, dtype=jnp.float32))
            if cfg.shared_experts:
                wide = cfg.shared_experts * cfg.expert_width
                if gated:
                    p.update(shared_gate=w(d, wide))
                p.update(shared_up=w(d, wide), shared_down=w(wide, d))
        elif experts is not None:
            if gated:
                p.update(w_gate=w(d, cfg.intermediate_size))
            p.update(w_up=w(d, cfg.intermediate_size), w_down=w(cfg.intermediate_size, d))
        layers.append(p)
    params = {"patch": w(cfg.patch_dim, d), "embed": w(cfg.vocab_size, d), "layers": layers,
              "norm": gain(d)}
    if not cfg.tie_embedding:
        params["head"] = w(d, cfg.vocab_size)
    if cfg.norm == "layer":
        params["norm_b"] = w(d)
    if cfg.exit_gate:  # lambda = sigmoid(h w + b): near 1/2 under these draws, seen and not saturated
        params["exit_gate"] = {"w": w(d), "b": jnp.zeros((), jnp.float32)}
    return params


def _index_params(cfg: DecoderConfig, w, gain, q_from: int) -> dict:
    """The indexer's matrices, its queries projected from ``q_from`` wide
    rows; a LayerNorm on the key has a bias beside its gain."""
    d, di = cfg.hidden_size, cfg.indexer_head_dim
    p = {"idx_wq": w(q_from, cfg.indexer_heads * di), "idx_wk": w(d, di),
         "idx_k_norm": gain(di), "idx_ww": w(d, cfg.indexer_heads)}
    if cfg.indexer_key_norm == "layer":
        p["idx_k_bias"] = w(di)
    return p


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------

def rms_norm(u, g, eps: float):
    """``u / sqrt(mean(u^2) + eps) * g`` over the last axis, in float32."""
    u = u.astype(jnp.float32)
    return u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)


def frame_positions(panels: int, rows: int, cols: int, prompt_len: int) -> np.ndarray:
    """``[S, 3]`` positions ``(t, h, w)``: the patch of panel ``p``, row
    ``r``, column ``c`` sits at ``(p, r, c)`` (a panel is to the detector
    what a frame is to a video: a disjoint sensor), panel-major as
    ``patchify_panels`` orders them; prompt token ``i`` has all three equal
    to ``max(panels, rows, cols) + i``."""
    p, r, c = np.meshgrid(np.arange(panels), np.arange(rows), np.arange(cols), indexing="ij")
    patches = np.stack([p.ravel(), r.ravel(), c.ravel()], axis=1)
    text = max(panels, rows, cols) + np.arange(prompt_len)
    return np.concatenate([patches, np.stack([text] * 3, axis=1)]).astype(np.int32)


def rotary_angles(pos, theta: float, pairs: int, sections=None, yarn: Optional[Yarn] = None):
    """``[S, pairs]`` angles: pair ``i`` turns by ``pos * theta**(-i/pairs)``
    (under ``yarn``, by its blended frequency: :meth:`Yarn.inv_freq`);
    with ``sections`` (multimodal rotary) ``pos`` is ``[S, 3]`` and pair
    ``i`` reads the position component of the section it falls in."""
    if yarn is not None:
        inv_freq = yarn.inv_freq(theta, pairs)
    else:
        inv_freq = theta ** (-np.arange(pairs, dtype=np.float64) / pairs)
    if sections is None:
        return jnp.asarray(pos, jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    which = np.repeat(np.arange(len(sections)), sections)  # [pairs] -> component
    return jnp.asarray(pos, jnp.float32)[:, which] * jnp.asarray(inv_freq, jnp.float32)


def rotate(x, angles):
    """``x [S, heads, 2*pairs]`` turned by ``angles [S, pairs]``; pair
    ``i`` is components ``(i, i + pairs)`` (the rotate-half convention)."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mm(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _kernel_turns(cfg: DecoderConfig, angles) -> bool:
    """Whether a grouped-query layer's rotary is the attention KERNEL's: it
    has one, its heads are whole lane blocks (a half-swap is then a rotation
    of whole vregs), and it runs the batched maskless or windowed kernel (no
    selection's mask). Laguna's two kinds of layer and the looped reader's;
    LFM2's heads of 64, keye's selection and granite (no rotary) are turned,
    or not, by :func:`_projections`."""
    return (angles is not None and cfg.head_dim % 128 == 0 and not cfg.indexer_heads
            and cfg.block_select is None)


def _rotary_scales(cfg: DecoderConfig, windowed: bool) -> Tuple[float, float]:
    """Grouped-query attention's two scalars: the softmax scale on q (with
    YaRN's ``mscale**2``) and YaRN's factor on a turned part (the full layers'
    alone: a ``windowed`` layer's rotary is plain)."""
    yarn = None if windowed else cfg.rope_yarn
    return (cfg.softmax_scale * (yarn.softmax_scale if yarn else 1.0),
            yarn.rotary_scale if yarn else 1.0)


def _projections(p, x, angles, cfg: DecoderConfig, windowed: bool = False):
    """Grouped-query attention's ``(a, q, k, v)`` from ``x [T, D]``, each
    ``[T, heads * head_dim]``, the query heads as many as THIS layer's
    ``wq`` has, and where the output is gated ``sigmoid(a W_G) [T, heads]``
    float32 after them. ``angles [T, pairs]`` (``None`` where the model has no
    rotary: q and k then pass as their products left them) turn the leading ``2 * pairs``
    components of a head (the layer type's rotary: all of a head, or the
    full layers' partial one) and the rest passes as it is; under YaRN (the
    full layers' alone: a ``windowed`` layer's rotary is plain) the turned
    part is multiplied by ``rotary_scale``, the cosines' and sines' factor.
    Where the kernel turns (:func:`_kernel_turns`) q and k leave FLOAT32,
    unturned and unscaled, token-major: exactly what ``W_q``'s and ``W_k``'s
    products wrote (PR 63), and the kernel turns, scales and rounds them once,
    as here (until then XLA sliced the two 64-lane halves of every head out of
    the float32 product into copies of their own, turned them in lane-padded
    passes and wrote the bf16 heads in a layout of its choosing)."""
    s = x.shape[0]
    dt = p["wq"].dtype  # the activations' type: x's own, but where the stream is kept in float32
    a = rms_norm(x, p["norm1"], cfg.rms_eps).astype(dt) if cfg.pre_norm else x.astype(dt)
    # the q / k norm: none, a gain a head's component, or over the WHOLE projection before the cut
    whole = cfg.qk_norm and cfg.qk_norm_span == "projection"
    a_head = cfg.qk_norm and not whole

    def normed(u, g, heads):
        u = rms_norm(u, p[g], cfg.rms_eps) if whole else u
        u = u.reshape(s, heads, cfg.head_dim)
        return rms_norm(u, p[g], cfg.rms_eps) if a_head else u

    q = normed(_mm(a, p["wq"]), "q_norm", -1)
    k = normed(_mm(a, p["wk"]), "k_norm", cfg.num_kv_heads)
    scale, turned = _rotary_scales(cfg, windowed)  # the softmax scale rides on q
    kept = _kernel_turns(cfg, angles)  # float32: the kernel rounds what it has turned
    if angles is None:
        q = q * scale
    elif not kept:
        turn = dict(angles=angles, width=2 * angles.shape[-1], scale=turned)
        q = _turn_leading(q, **turn) * scale
        k = _turn_leading(k, **turn)
    v = _mm(a, p["wv"])
    q, k = (u.reshape(s, -1) if kept else u.reshape(s, -1).astype(dt) for u in (q, k))
    out = (a, q, k, v.astype(dt))
    if cfg.attn_gate == "elementwise":  # the gate's PRE-activation [T, H * head_dim], as wide as o:
        out += (_mm(a, p["w_attn_gate"]).astype(dt),)  # its sigmoid rides in W_o's operand
    elif cfg.attn_gate:
        out += (jax.nn.sigmoid(_mm(a, p["w_attn_gate"])),)
    return out


def layer_norm(u, g, b, eps: float):
    """``(u - mean(u)) / sqrt(var(u) + eps) * g + b`` over the last axis, in
    float32: the one index key's norm under a key selection over latent
    attention, and the blocks' and the final norm of a model whose
    ``DecoderConfig.norm`` is ``"layer"`` (:func:`block_norm`)."""
    u = u.astype(jnp.float32)
    u = u - jnp.mean(u, axis=-1, keepdims=True)
    return (u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
            * g.astype(jnp.float32) + b.astype(jnp.float32))


def block_norm(x, p, name: str, cfg: DecoderConfig):
    """The norm ``name`` of a block (or the final one) on ``x``, float32: an
    RMS norm with the gain ``p[name]`` or, under ``cfg.norm == "layer"``, a
    LayerNorm with that gain and the bias ``p[name + "_b"]``."""
    if cfg.norm == "layer":
        return layer_norm(x, p[name], p[f"{name}_b"], cfg.rms_eps)
    return rms_norm(x, p[name], cfg.rms_eps)


def _turn_leading(x, angles, width: int, scale: float = 1.0):
    """:func:`rotate` on the first ``width`` components of ``x``'s last
    axis (0, or the whole width: on all of them), the turned part times
    ``scale``; the rest passes as it is."""
    whole = not width or width == x.shape[-1]
    turned = rotate(x if whole else x[..., :width], angles)
    if scale != 1.0:
        turned = turned * scale
    return turned if whole else jnp.concatenate([turned, x[..., width:]], axis=-1)


def _indexer(p, a, idx_angles, cfg: DecoderConfig, q_from=None):
    """The selection of one sequence from the layer's normed input ``a``:
    index queries from ``q_from`` (default: ``a``), ONE normed index key and
    the heads' weights from ``a`` -> :func:`sparse_attention.select_keys`'
    mask and flags."""
    s = a.shape[0]
    h, d = cfg.indexer_heads, cfg.indexer_head_dim
    q = _turn_leading(_mm(a if q_from is None else q_from, p["idx_wq"]).reshape(s, h, d),
                      idx_angles, cfg.indexer_rope_dim)
    if cfg.indexer_key_norm == "layer":
        k = layer_norm(_mm(a, p["idx_wk"]), p["idx_k_norm"], p["idx_k_bias"], cfg.rms_eps)
    else:
        k = rms_norm(_mm(a, p["idx_wk"]), p["idx_k_norm"], cfg.rms_eps)
    k = _turn_leading(k[:, None, :], idx_angles, cfg.indexer_rope_dim)
    w = _mm(a, p["idx_ww"]) * h ** -0.5
    # a call of its own: the kernel is `select_keys` in a device trace
    return jax.jit(sa.select_keys, static_argnames=("topk", "block_q", "block_k"))(
        jnp.transpose(q, (1, 0, 2)).astype(a.dtype), k[:, 0].astype(a.dtype), w,
        topk=cfg.topk, block_q=cfg.q_tile, block_k=cfg.kv_tile)


def _dense_mlp(p, b):
    """``MLP(b)``: gated SiLU where ``p`` has a ``w_gate``, the ungated
    ``relu(b W_up)^2 W_down`` where it has none (``moe.hidden_rows``)."""
    h = hidden_rows(lambda w: _mm(b, w), p.get("w_gate"), p["w_up"]).astype(b.dtype)
    return _mm(h, p["w_down"]).astype(b.dtype)


def gated_short_conv(p, x, batch: int, cfg: DecoderConfig):
    """LFM2's operator on ``x [B*S, D]`` -> ``x + Op``: with ``[B | C | z]
    = rms(x) W_in``, a causal depthwise convolution of ``conv_taps`` taps
    over ``u = B * z`` (tap ``j`` meets ``u[t - (taps - 1) + j]``, zeros
    before each of the ``batch`` sequences, no bias, no activation), gated
    by ``C``, then ``W_out``. The gates and taps are one pass between the
    two matrix products (``ops/short_conv.gated_conv_taps``)."""
    dt = x.dtype
    a = rms_norm(x, p["norm1"], cfg.rms_eps).astype(dt)
    y = gated_conv_taps(_mm(a, p["w_in"]).astype(dt), p["conv_w"], seq_len=x.shape[0] // batch)
    return x + _mm(y, p["w_out"]).astype(dt)


def _latent_projections(p, x, angles, cfg: DecoderConfig):
    """``x [T, D]`` -> ``(q_nope [T, H*dn], q_rope [T, H*dr], k_nope, k_rope
    [T, dr], v)``, and under a key selection the normed input and the
    normed query rank ``(a [T, D], c_q [T, rq])`` after them, and last,
    where the output is gated, ``sigmoid(a W_G) [T, H]`` float32: the queries
    through their normed low rank (``q_lora_rank`` 0: from the normed input,
    of full rank), the keys' and values' per-head parts
    decompressed from the normed latent and the ONE rotary key (not normed),
    turned. ``q_rope`` leaves FLOAT32 and UNTURNED, as ``W_uq``'s rotary
    columns' product wrote it (PR 61): the attention kernel turns its query
    tile by the step's tables (:func:`turn_tables`), scales it and rounds it
    once, as ``rotate`` here did. The softmax scale (with YaRN's
    ``mscale**2``) rides on ``q_nope``; ``q_rope``'s is the kernel's
    (:func:`_latent_scales`).

    Every array leaves the matrix product that makes it in the layout the
    attention kernel reads (PR 48), token-major: ``W_uq``'s COLUMNS are cut
    into the heads' ``dn`` and ``dr`` parts (a 38 MB weight at 64 heads) and
    two products write ``q_nope`` in bf16, scaled in the product's epilogue,
    and the rotary part, head-major ``[H, 1, T, dr]`` float32 (the kernel's
    operand: the transpose is that product's layout, no op; ONE product of
    three dimensions, ``[T, rq] x [rq, H, dr]``: written two-dimensional
    and reshaped, XLA compiled six of kimi's seven layers, in the whole
    step alone, to a column-major product and a copy of it, 1.4 ms a layer:
    PR 61); where keys and values are equally wide (``dn == dv``: every
    published latent model) ``k_nope`` is the ONE product ``[T, H*(dn +
    dv)]``, head ``h``'s keys at column block ``2h`` and its values
    at ``2h + 1``, and ``v`` is ``None``
    (``sparse_attention.masked_gqa_attention`` reads both from it); else
    two arrays cut from it. Cutting the ACTIVATIONS ``[T, H, dn + dr]`` and
    ``[T, H, dn + dv]``, as until PR 48, made XLA write the query product in
    float32, column-major, and slice, relay and convert it: with the
    head-major transposes around the kernel eleven array-sized passes a
    layer that computed nothing. One layer alone on the v5e (projections,
    attention, ``W_o``; my chip runs, PR 48): kimi's 66.97 -> 59.84 ms,
    dsv32's with its indexer 84.32 -> 70.63; with the rotary query's turn
    in the kernel (the ops' sum; my chip runs, PR 61) 60.04 -> 55.40 and
    70.70 -> 66.45."""
    t, dt = x.shape[0], x.dtype
    h, dn, dr, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    scale, turned = _latent_scales(cfg)
    a = rms_norm(x, p["norm1"], cfg.rms_eps).astype(dt)
    if cfg.q_lora_rank:
        c_q = rms_norm(_mm(a, p["wq_a"]), p["q_a_norm"], cfg.rms_eps).astype(dt)
        wq_b = p["wq_b"].reshape(-1, h, dn + dr)
    else:
        c_q, wq_b = a, p["wq"].reshape(-1, h, dn + dr)
    q_nope = _mm(c_q, wq_b[..., :dn].reshape(-1, h * dn)) * scale
    q_rope = jnp.einsum("tr,rhd->thd", c_q, wq_b[..., dn:],  # float32: the kernel turns it
                        preferred_element_type=jnp.float32).reshape(t, -1)
    down = _mm(a, p["wkv_a"])  # [T, latent | the rotary key]
    c_kv = rms_norm(down[:, :cfg.kv_lora_rank], p["kv_a_norm"], cfg.rms_eps).astype(dt)
    k_rope = rotate(down[:, None, cfg.kv_lora_rank:], angles)[:, 0] * turned
    kv = _mm(c_kv, p["wkv_b"]).astype(dt)  # a head's keys, then its values
    if dn == dv:
        k_nope, v = kv, None
    else:
        kv = kv.reshape(t, h, dn + dv)
        k_nope, v = kv[..., :dn].reshape(t, -1), kv[..., dn:].reshape(t, -1)
    out = (q_nope.astype(dt), q_rope, k_nope, k_rope.astype(dt), v)
    if cfg.indexer_heads:
        out += (a, c_q)  # what an indexer reads
    if cfg.attn_gate:
        out += (jax.nn.sigmoid(_mm(a, p["w_attn_gate"])),)
    return out


def _latent_scales(cfg: DecoderConfig) -> Tuple[float, float]:
    """Latent attention's two scalars: the softmax scale on q (with YaRN's
    ``mscale**2``) and YaRN's factor on a turned part (its cosines' and sines')."""
    yarn = cfg.rope_yarn
    return (cfg.head_dim ** -0.5 * (yarn.softmax_scale if yarn else 1.0),
            yarn.rotary_scale if yarn else 1.0)


def turn_tables(angles, head_dim: int = 0):
    """``angles [T, pairs]`` -> ``([cos | cos], [sin | sin])``, each ``[T,
    2*pairs]`` float32: what turns a head by the rotate-half convention as
    ``x * [cos | cos] + [-x2 | x1] * [sin | sin]`` (:func:`rotate`, no half
    sliced). The causal kernel turns its shared query tile by them. With
    ``head_dim`` the tables of a WHOLE head ``[T, head_dim]``, the sine
    SIGNED: ``[cos | cos | 1]`` and ``[-sin | sin | 0]``, by which a head
    turns as ``x * cos + rolled * sin`` with no half negated, ``rolled`` its
    lanes rotated by half the turned width, and the lanes past ``2*pairs``
    (a partial rotary's) pass as they are
    (``sparse_attention._turned_head``: the grouped-query kernel's turn)."""
    if not head_dim:
        return tuple(jnp.concatenate([f(angles)] * 2, axis=-1) for f in (jnp.cos, jnp.sin))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    passing = (angles.shape[0], head_dim - 2 * angles.shape[1])
    return (jnp.concatenate([cos, cos, jnp.ones(passing, jnp.float32)], axis=-1),
            jnp.concatenate([-sin, sin, jnp.zeros(passing, jnp.float32)], axis=-1))


def gated(x, o, gate, wo):
    """``x + (o [T, H*dv], each head times its own scalar of gate [T, H]) W_o``:
    the head-wise output gate, fused into ``W_o``'s operand."""
    o = o.reshape(x.shape[0], gate.shape[1], -1) * gate[:, :, None]
    return x + _mm(o.astype(x.dtype).reshape(x.shape[0], -1), wo).astype(x.dtype)


def latent_attention(p, x, angles, batch: int, cfg: DecoderConfig, idx_angles=None,
                     onto: bool = True):
    """DeepSeek-V3's operator on ``x [B*S, D]`` -> ``(x + Op, live,
    causal)`` (``onto`` False: ``Op`` alone, the BRANCH's output, where what a
    branch reads is not what it is added onto: ``cfg.hc_mult``), the last
    two the layer's statistics tiles, prefill in
    the DECOMPRESSED form: every head has its own keys and values (``2 *
    (head_dim + v_head_dim)`` FLOPs a causal pair and head; the absorbed
    form, scores against the latent itself, is decode's), and a score is
    ``q_nope . k_nope + q_rope . k_rope`` with the rotary key read from its
    one ``[B, S, dr]`` array (``sparse_attention.masked_gqa_attention``'s
    shared part), never written ``H`` times over. Under a learned key
    selection (DeepSeek-V3.2's: an indexer fed by the query's normed low
    rank, the same ``Sel(t)`` in every head) the same kernel takes the
    selection's mask: still the decompressed form over every causal tile,
    which at a selection of 2,048 of 8,704 does less arithmetic than the
    absorbed form over the selected pairs alone. Nothing array-sized runs
    between the projections' products, the kernel and ``W_o`` (PR 48): q,
    k, v and o are column blocks of token-major arrays, the kernel's ``[B,
    S, H*dv]`` output is the array ``W_o`` contracts, and only the
    ``dr``-wide rotary query is head-major (a 64-lane block of ``[S, H*64]``
    Mosaic does not take), written so by its own product: float32 and
    unturned, and the kernel turns a query tile of it where it reads it
    (PR 61; until then XLA turned it in two float32 passes over lanes a half
    and a quarter full and wrote a third, bf16, head-major: 3.7 GB of
    traffic a layer for 143 MB of query). The ONE rotary key stays XLA's
    to turn: every key step reads it. Under the scopes ``proj`` (both
    low-rank paths, their norms, the key's rotary, ``W_o``), ``indexer``
    (its three projections and ``select_keys``) and ``latent_attn`` (the
    attention itself)."""
    s = x.shape[0] // batch
    with jax.named_scope("proj"):
        q_nope, q_rope, k_nope, k_rope, v, *read = jax.jit(
            _latent_projections, static_argnums=3)(p, x, angles, cfg)
        tables = jax.jit(turn_tables)(angles)  # every layer's: the step's ONE pair
    scale, turned = _latent_scales(cfg)  # the kernel's, on the rotary query after its turn
    selection = ()  # the mask, where there is one: the kernel's fourth operand
    if cfg.indexer_heads:
        if batch != 1:
            raise ValueError(f"a learned key selection is per sequence: batch {batch} is not 1")
        with jax.named_scope("indexer"):
            mask, flags = jax.jit(_indexer, static_argnums=3)(p, read[0], idx_angles, cfg,
                                                              q_from=read[1])
            live, causal = sa.live_tiles(flags, s)
            selection = (mask,)
    else:
        live = causal = batch * sa.causal_tile_count(s)  # every earlier key is attended
    with jax.named_scope("latent_attn"):
        attend = jax.jit(sa.masked_gqa_attention,
                         static_argnames=("num_kv_heads", "block_q", "block_k", "shared_scale"))

        def rows(u):  # of each sequence
            return u if u is None else u.reshape(batch, s, -1)

        o = attend(rows(q_nope), rows(k_nope), rows(v), *selection, num_kv_heads=cfg.num_heads,
                   block_q=cfg.causal_q_tile, block_k=cfg.causal_kv_tile, q_shared=rows(q_rope),
                   k_shared=rows(k_rope), shared_turn=tables, shared_scale=scale * turned)
    with jax.named_scope("proj"):
        if not onto:  # (from_mapping: no output gate under hyper-connections)
            x = jax.jit(lambda o, wo: _mm(o, wo).astype(o.dtype))(o.reshape(x.shape[0], -1), p["wo"])
        elif cfg.attn_gate:  # each head's output under its own scalar, then W_o
            x = jax.jit(gated)(x, o, read[-1], p["wo"])
        else:
            x = jax.jit(lambda x, o, wo: x + _mm(o, wo).astype(x.dtype))(
                x, o.reshape(x.shape[0], -1), p["wo"])
    return x, live, causal


def conv_silu(u, taps_w, seq_len: int, bias=None):
    """``silu(conv(u) + bias)`` on ``u [T, C]`` (whole sequences of ``seq_len``
    rows): a causal depthwise convolution, tap ``j`` of ``taps_w [C, taps]``
    on ``u[t - (taps - 1) + j]``, zeros before each sequence's first row,
    ``bias [C]`` where the model has one; float32 inside, ``u``'s type out:
    ONE kernel (``ops/short_conv.conv_silu_taps``)."""
    return conv_silu_taps(u, taps_w, bias, seq_len=seq_len)


def _linear_projections(p, x, cfg: DecoderConfig):
    """``x [T, D]`` -> ``([q | k | v] [T, 3*H*d]`` before their convolution,
    ``f [T, H*d]`` float32 (the decay's pre-activation: a log-decay is summed
    over a chunk's rows, so it is never rounded to bf16), ``z [T, H*d]``
    (the output gate's), ``beta [T, H]`` float32)."""
    dt = x.dtype
    a = rms_norm(x, p["norm1"], cfg.rms_eps).astype(dt)
    return (_mm(a, p["w_qkv"]).astype(dt), _mm(a, p["w_f"]), _mm(a, p["w_z"]).astype(dt),
            jax.nn.sigmoid(_mm(a, p["w_beta"])))


def _delta_net_projections(p, x, cfg: DecoderConfig):
    """The same where ONE decay a head is projected (``w_f [D, H]``): ``x [T,
    D]`` -> ``(q, k [T, H*dl]``, ``v [T, H*d_v]`` before their convolutions,
    ``f [T, H]`` float32, ``z [T, H*d_v]``, ``beta [T, H]`` float32,
    ``sigmoid(.)`` times ``linear_beta_scale``), from the normed input where
    the block has a norm before its branch, else from the stream as it is.
    The weights are held at the published shapes, ``W_q, W_k [D, H*d_k]``: a
    head's ``d_k`` (96) columns are laid at whole lane tiles (``dl`` 128:
    ``delta_rule.lanes_a_head`` on the WEIGHT, 11 M elements, where the
    product's ``[T, H*d_k]`` result would cost three array-sized passes of
    XLA's: a slice, a pad and a relayout, compiled and read), so ``q`` and
    ``k`` leave their products as the kernel reads them: a zero column
    changes no convolution, no L2 norm, no product and no row of a state."""
    dt, heads = p["w_q"].dtype, cfg.num_heads
    a = rms_norm(x, p["norm1"], cfg.rms_eps).astype(dt) if cfg.pre_norm else x.astype(dt)
    q, k = (_mm(a, lanes_a_head(p[w], heads)).astype(dt) for w in ("w_q", "w_k"))
    return (q, k, _mm(a, p["w_v"]).astype(dt), _mm(a, p["w_f"]), _mm(a, p["w_z"]).astype(dt),
            cfg.linear_beta_scale * jax.nn.sigmoid(_mm(a, p["w_beta"])))


def _lanes_conv_silu(u, taps_w, seq_len: int, heads: int):
    """:func:`conv_silu` on ``u [T, H*dl]`` with the published taps ``[H*d_k,
    taps]`` laid out a head at whole lane tiles as ``u``'s columns are."""
    return conv_silu(u, lanes_a_head(taps_w.T, heads).T, seq_len)


def _fixed_decay_projections(p, x, cfg: DecoderConfig):
    """``x [T, D]`` -> ``(q, k [T, H*d]`` FLOAT32, as ``W_q``'s and ``W_k``'s
    products wrote them: the kernel norms, turns and rounds them;
    ``v [T, H*d]``, ``z [T, H*d]`` (the output gate's pre-activation))."""
    dt = p["w_q"].dtype
    a = rms_norm(x, p["norm1"], cfg.rms_eps).astype(dt)
    return _mm(a, p["w_q"]), _mm(a, p["w_k"]), _mm(a, p["w_v"]).astype(dt), _mm(a, p["w_z"]).astype(dt)


def fixed_decay_attention(p, x, angles, batch: int, cfg: DecoderConfig):
    """Linear attention with a decay that is a CONSTANT of the head
    (Lightning Attention-2, as MiniCPM-SALA's ``lightning-attn`` layers have
    it) on ``x [B*S, D]`` -> ``x + residual_multiplier * Op``: with ``a =
    rms(x)``, ``q, k, v = a W_q, a W_k, a W_v``; per head ``q`` and ``k``
    RMS-normed over the head's columns (a gain each) and, where the layer has
    a rotary (``angles [B*S, pairs]``), turned over the whole head; ``S_t =
    lambda_h S_{t-1} + k_t v_t^T`` (float32, 0 at every sequence's start),
    ``o_t = d^-1/2 S_t^T q_t``; the output normed per head and THEN gated by
    ``sigmoid(a W_z)``; then ``W_o``. Under the scopes ``proj`` (the norm, the
    four products, the rotary's two tables, ``W_o``) and ``lightning`` (the
    two norms, the turn, the recurrence, the output's norm and gate: ONE
    kernel, ``ops/lightning.lightning_attention``)."""
    s, d = x.shape[0] // batch, cfg.linear_head_dim
    with jax.named_scope("proj"):
        q, k, v, z = jax.jit(_fixed_decay_projections, static_argnums=2)(p, x, cfg)
        turn = None if angles is None else jax.jit(turn_tables, static_argnums=1)(angles, d)
    with jax.named_scope("lightning"):
        o = lightning.lightning_attention(
            q, k, v, z, jnp.asarray(lightning.decay_slopes(cfg.num_heads)), p["q_norm"], p["k_norm"],
            p["o_norm"], turn, seq_len=s, heads=cfg.num_heads, eps=cfg.rms_eps, scale=d ** -0.5,
            chunk=cfg.linear_chunk)
    with jax.named_scope("proj"):
        return jax.jit(_onto, static_argnums=3)(x, o, p["wo"], cfg.residual_multiplier)


def linear_attention(p, x, batch: int, cfg: DecoderConfig):
    """Linear attention by the gated delta rule on ``x [B*S, D]`` -> ``x +
    Op`` (the branch normed before it is added where the block has a norm
    after it), in one of TWO forms told apart by ``cfg.linear_decay`` alone
    (what :func:`init_params` draws by too): ``"channel"``, a decay per head
    AND channel from ``w_f [D, H*d]`` (Kimi Delta Attention, as Ling-3.0's
    linear layers have it), or ``"head"``, ONE a head from ``w_f [D, H]``
    (Gated DeltaNet, as Olmo-Hybrid's). With ``a`` the layer's input
    (normed where the block norms it), ``q, k, v = a W_q, a W_k, a W_v`` (one
    ``W_qkv`` per channel; three matrices per head) each through
    a causal depthwise convolution of ``conv_taps`` taps and a SiLU
    (:func:`conv_silu`; zeros before each sequence); per head ``q`` and ``k``
    L2-normed, the step size ``linear_beta_scale * sigmoid(a W_beta)`` one a
    head; the delta rule's state, ``[d_k, d_v]`` float32 a head, starts at 0
    with every sequence. Per channel: the log-decay ``linear_decay_floor *
    sigmoid(exp(A) (a W_f + b))``, the output normed per head (``o_norm``)
    and gated by ``sigmoid(a W_z)``. Per head: the log-decay ``-exp(A_log)
    softplus(a W_f + dt_bias)``, unbounded below, keys ``linear_head_dim``
    and values ``linear_value_dim`` wide, the output normed per head and THEN
    gated by ``silu(a W_z)``. Then ``W_o``. No rotary. Under
    the scopes ``proj`` (the norm, the four products, ``W_o`` with the
    branch's norm as its epilogue), ``conv`` (the three convolutions and their
    SiLU: one kernel's pass over ``[q | k | v]``, or over q, k and v each:
    ``ops/short_conv.conv_silu_taps``) and the rule's kernel under a scope of its
    own, ``kda`` or ``gdn`` (the gate, the L2 norms, the recurrence and the
    output's norm and gate: ONE kernel, ``ops/delta_rule.gated_delta_rule``
    or ``gated_delta_net``)."""
    s = x.shape[0] // batch
    if cfg.linear_decay == "head":  # ONE decay a head
        with jax.named_scope("proj"):
            q, k, v, f, z, beta = jax.jit(_delta_net_projections, static_argnums=2)(p, x, cfg)
        with jax.named_scope("conv"):
            q, k = (jax.jit(_lanes_conv_silu, static_argnums=(2, 3))(u, p[w], s, cfg.num_heads)
                    for u, w in ((q, "conv_q"), (k, "conv_k")))
            v = jax.jit(conv_silu, static_argnums=2)(v, p["conv_v"], s)
        with jax.named_scope("gdn"):
            o = gated_delta_net(q, k, v, f, z, beta, p["a_log"], p["dt_bias"], p["o_norm"],
                                seq_len=s, heads=cfg.num_heads, key_dim=cfg.linear_head_dim,
                                eps=cfg.rms_eps, chunk=cfg.linear_chunk)
    else:
        with jax.named_scope("proj"):
            qkv, f, z, beta = jax.jit(_linear_projections, static_argnums=2)(p, x, cfg)
        with jax.named_scope("conv"):
            qkv = jax.jit(conv_silu, static_argnums=2)(qkv, p["conv_w"], s)
        with jax.named_scope("kda"):
            o = gated_delta_rule(qkv, f, z, beta, p["decay_a"], p["decay_b"], p["o_norm"], seq_len=s,
                                 heads=cfg.num_heads, lower=cfg.linear_decay_floor, eps=cfg.rms_eps,
                                 chunk=cfg.linear_chunk)
    with jax.named_scope("proj"):
        if cfg.sandwich:
            return jax.jit(_onto_normed, static_argnums=4)(x, o, p["wo"], p["norm1_post"], cfg.rms_eps)
        return jax.jit(lambda x, o, wo: x + _mm(o, wo).astype(x.dtype))(x, o, p["wo"])


def _onto(x, o, wo, by: float):
    """``x + by * (o W_o)``: a branch under its residual multiplier, which
    rides in the product's epilogue; the sum is float32, and stays so where
    the stream is (``DecoderConfig.stream_dtype``)."""
    return (x + by * _mm(o, wo)).astype(x.dtype)


def _gated_onto(x, o, z, wo, by: float):
    """``x + by * ((o * sigmoid(z)) W_o)``: an attention branch under an
    ELEMENTWISE output gate (``z [T, H * head_dim]``, its pre-activation), the
    gate in ``W_o``'s operand (float32, rounded once) and the residual
    multiplier in its epilogue."""
    y = (o.astype(jnp.float32) * jax.nn.sigmoid(z.astype(jnp.float32))).astype(wo.dtype)
    return (x + by * _mm(y, wo)).astype(x.dtype)


def _ssm_projections(p, x, cfg: DecoderConfig):
    """``x [T, D]`` -> ``(z [T, H*P]`` (the gate's), ``[x | B | C] [T, H*P +
    2*G*N]`` before their convolution (``G`` groups of B and C), ``dt [T, H]`` float32 (the step's
    pre-activation: a log-decay is summed over a chunk's rows, so it is
    never rounded to bf16)): ``W_in``'s COLUMNS are cut, not the product
    (:func:`_latent_projections` says why), so each array leaves its own
    product in the type and layout the next pass reads."""
    w = p["w_in"]
    dt = w.dtype  # the activations' type (the stream x itself: `cfg.stream_dtype`)
    wide = cfg.ssm_heads * cfg.ssm_head_dim
    conv = w.shape[1] - wide - cfg.ssm_heads  # H*P + 2*G*N: a B and a C a group, from the shapes
    a = rms_norm(x, p["norm1"], cfg.rms_eps).astype(dt)
    return (_mm(a, w[:, :wide]).astype(dt), _mm(a, w[:, wide:wide + conv]).astype(dt),
            _mm(a, w[:, wide + conv:]))


def state_space(p, x, batch: int, cfg: DecoderConfig):
    """Mamba-2's layer, as Granite-4.0-H has it, on ``x [B*S, D]`` -> ``x +
    residual_multiplier * Op``: with ``a = rms(x)``, ``[z | xBC | dt] = a
    W_in``; ``xBC`` through a causal depthwise convolution of ``conv_taps``
    taps with a bias and a SiLU (:func:`conv_silu`; zeros before each
    sequence); ``[x | B | C] = xBC``, the selective scan over ``ssm_heads``
    heads with ONE ``B`` and ``C`` for all, or one a group of heads where the
    shapes hold ``ssm_groups`` of them (the state starts at 0 with every
    sequence), the skip ``D x``, the gate ``silu(z)`` and THEN the RMS norm
    over all of a token's channels, or over each group's; then ``W_out``. No rotary, no bias in
    the products. Under the scopes ``proj`` (the norm, ``W_in``'s three
    products, ``W_out``), ``conv`` (the convolution, its bias and SiLU: one
    kernel's pass over ``[T, H*P + 2*G*N]``, ``ops/short_conv.conv_silu_taps``) and ``ssd`` (the step's softplus, the
    decays, the scan, the skip, the gate and the norm: ONE kernel,
    ``ops/ssd.ssd_scan``). Each part is jitted by NAME: the layers of a
    model trace and lower once, however many they are."""
    s = x.shape[0] // batch
    with jax.named_scope("proj"):
        z, xbc, dt = jax.jit(_ssm_projections, static_argnums=2)(p, x, cfg)
    with jax.named_scope("conv"):
        xbc = jax.jit(conv_silu, static_argnums=2)(xbc, p["conv_w"], s, p.get("conv_b"))
    with jax.named_scope("ssd"):
        o = ssd_scan(xbc, z, dt, p["dt_bias"], p["a_log"], p["d_skip"], p["ssm_norm"], seq_len=s,
                     heads=cfg.ssm_heads, state=cfg.ssm_state, eps=cfg.rms_eps)
    with jax.named_scope("proj"):
        return jax.jit(_onto, static_argnums=3)(x, o, p["w_out"], cfg.residual_multiplier)


def _scan_projections(p, x, cfg: DecoderConfig):
    """``x [T, D]`` -> ``(xs, z) [T, C]`` each: ``W_in``'s two halves, its
    COLUMNS cut and not the product (:func:`_ssm_projections` says why)."""
    w, wide = p["w_in"], cfg.scan_channels
    dt = w.dtype
    a = block_norm(x, p, "norm1", cfg).astype(dt)
    return _mm(a, w[:, :wide]).astype(dt), _mm(a, w[:, wide:]).astype(dt)


def _scan_operands(p, u, cfg: DecoderConfig):
    """``u [T, C]`` (after its convolution) -> ``(delta [T, C]`` float32,
    ``[B | C | 0] [T, 128])``: ``[delta | B | C] = u W_x`` as two products
    of ``W_x``'s columns (the low rank; ``B`` and ``C``, laid on the WEIGHT
    at the lane tile the kernel reads), and the step's pre-activation
    ``delta W_dt``, float32: a log-decay's factor is never rounded to bf16."""
    w, rank = p["w_x"], cfg.scan_dt_rank
    dt = w.dtype
    low = _mm(u, w[:, :rank]).astype(dt)
    onto_tile = jnp.pad(w[:, rank:], ((0, 0), (0, SCAN_LANES - 2 * cfg.ssm_state)))
    return _mm(low, p["w_dt"]), _mm(u, onto_tile).astype(dt)


def selective_state_space(p, x, batch: int, cfg: DecoderConfig, keep: bool = False):
    """Mamba-1's layer on ``x [B*S, D]`` -> ``x + Op`` (with ``keep``: ``(x +
    Op, y)``, the scan's output with its skip and BEFORE its gate, ``[B*S,
    C]``: what a later gated memory unit reads): with ``a`` the block's normed
    input, ``[xs | z] = a W_in``; ``u = silu(conv(xs) + b_c)`` (:func:`conv_silu`;
    zeros before each sequence); ``[delta | B | C] = u W_x``, ``Delta =
    softplus(delta W_dt + b_dt)``, ``A = -exp(A_log) [C, N]``; the selective
    scan with a decay a channel AND state (the state starts at 0 with every
    sequence), the skip ``D u`` and the gate ``silu(z)``; then ``W_out``. No
    bias in the products. Under the scopes ``proj`` (the norm, ``W_in``'s two
    products, ``W_x``'s two and ``W_dt``'s, ``W_out``), ``conv`` and
    ``selective_scan`` (the softplus, the recurrence, the skip and the gate:
    ONE kernel, ``ops/selective_scan.py``)."""
    s = x.shape[0] // batch
    with jax.named_scope("proj"):
        xs, z = jax.jit(_scan_projections, static_argnums=2)(p, x, cfg)
    with jax.named_scope("conv"):
        u = jax.jit(conv_silu, static_argnums=2)(xs, p["conv_w"], s, p["conv_b"])
    with jax.named_scope("proj"):
        delta, bc = jax.jit(_scan_operands, static_argnums=2)(p, u, cfg)
    with jax.named_scope("selective_scan"):
        o = selective_scan(u, delta, bc, z, -jnp.exp(p["a_log"].astype(jnp.float32)), p["d_skip"],
                           p["dt_bias"], seq_len=s, keep=keep)
    o, y = o if keep else (o, None)
    with jax.named_scope("proj"):
        x = jax.jit(_onto, static_argnums=3)(x, o, p["w_out"], 1.0)
    return (x, y) if keep else x


def gated_memory(p, x, memory, cfg: DecoderConfig):
    """A gated memory unit on ``x [T, D]`` -> ``x + (silu(a W_1) * m) W_2``,
    ``m [T, C]`` the scan output an earlier layer handed on, at the same
    tokens. One jitted part, under the scope ``gmu``."""
    def unit(p, x, memory):
        dt = p["w_1"].dtype
        a = block_norm(x, p, "norm1", cfg).astype(dt)
        g = (jax.nn.silu(_mm(a, p["w_1"])) * memory.astype(jnp.float32)).astype(dt)
        return x + _mm(g, p["w_2"]).astype(x.dtype)

    with jax.named_scope("gmu"):
        return jax.jit(unit)(p, x, memory)


def lambda_init(index: int) -> float:
    """Differential attention's ``lambda_init`` of the layer at (0-based) ``index``."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * index))


def _half_products(a, w, bias, heads: int, d: int, scale: float = 1.0):
    """``a W + b`` of the first and of the second half-heads, ``[T, heads * d]``
    each in ``a``'s type (times ``scale``): ``W [D, heads * 2 * d]`` holds a
    pair's two half-heads side by side, and its columns (and the bias's) are
    cut on the WEIGHT, never on the product."""
    out = []
    for c in (0, 1):
        y = _mm(a, w.reshape(-1, heads, 2, d)[:, :, c].reshape(-1, heads * d))
        if bias is not None:
            y = y + bias.reshape(heads, 2, d)[:, c].reshape(-1).astype(jnp.float32)
        out.append((y * scale if scale != 1.0 else y).astype(a.dtype))
    return out


def _diff_queries(p, a, cfg: DecoderConfig):
    """The normed rows ``a [T, D]`` -> ``(q1, q2) [T, P * d]``: the ``P`` query
    pairs' first and second half-heads, times the softmax scale, from
    ``W_qkv``'s leading columns or (cross attention) from ``W_q``."""
    w, bias = (p["w_q"], p.get("b_q")) if "w_q" in p else (p["w_qkv"], p.get("b_qkv"))
    wide = cfg.num_heads * cfg.head_dim
    return _half_products(a, w[:, :wide], None if bias is None else bias[:wide],
                          cfg.num_heads // 2, cfg.head_dim, cfg.softmax_scale)


def _diff_keys_values(p, a, cfg: DecoderConfig):
    """The normed rows ``a [T, D]`` -> ``(k1, k2 [T, G * d], v [T, G * 2d])``: the
    ``G`` key pairs' first and second half-heads and the pairs' values, both
    halves side by side as published, from ``W_qkv``'s columns after the queries'."""
    w, bias, d = p["w_qkv"], p.get("b_qkv"), cfg.head_dim
    lo = cfg.num_heads * d
    hi = lo + cfg.num_kv_heads * d
    k1, k2 = _half_products(a, w[:, lo:hi], None if bias is None else bias[lo:hi],
                            cfg.num_kv_heads // 2, d)
    v = _mm(a, w[:, hi:])
    return k1, k2, (v if bias is None else v + bias[hi:].astype(jnp.float32)).astype(a.dtype)


def _differ(o1, o2, p, cfg: DecoderConfig, index: int):
    """The two softmaxes' outputs ``o1, o2 [T, P * 2d]`` -> ``rms(o1 - lambda o2;
    gain) (1 - lambda_init) [T, P * 2d]``: ``lambda = exp(lq1 . lk1) - exp(lq2 .
    lk2) + lambda_init``, the RMS norm over each pair's ``2d`` columns."""
    f32 = jnp.float32
    first = lambda_init(index)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"].astype(f32) * p["lambda_k1"].astype(f32)))
           - jnp.exp(jnp.sum(p["lambda_q2"].astype(f32) * p["lambda_k2"].astype(f32))) + first)
    o = (o1.astype(f32) - lam * o2.astype(f32)).reshape(o1.shape[0], -1, 2 * cfg.head_dim)
    return (rms_norm(o, p["sub_norm"], cfg.rms_eps) * (1.0 - first)).astype(o1.dtype).reshape(o1.shape)


def _row_attention(q, k, v, at, g: int):
    """B single-row queries a sequence against ALL its keys, plain products:
    ``q [B, R, H*d]`` (scaled) at the positions ``at [R]`` of sequences whose
    keys are ``k [B, S, G*d]`` and values ``v [B, S, G*dv]`` -> ``[B, R, H*dv]``,
    query head ``h`` reading key head ``h // (H/G)``, a query the keys at or
    before its position."""
    b, r, _ = q.shape
    s = k.shape[1]
    k, v = k.reshape(b, s, g, -1), v.reshape(b, s, g, -1)
    q = q.reshape(b, r, g, -1, k.shape[-1])
    scores = jnp.einsum("brgpd,bsgd->bgprs", q, k, preferred_element_type=jnp.float32)
    open_ = jnp.arange(s)[None, :] <= jnp.asarray(at)[:, None]
    prob = jax.nn.softmax(jnp.where(open_, scores, sa.NEG_INF), axis=-1).astype(v.dtype)
    o = jnp.einsum("bgprs,bsge->brgpe", prob, v, preferred_element_type=jnp.float32)
    return o.reshape(b, r, -1).astype(v.dtype)


def diff_attention(p, x, batch: int, cfg: DecoderConfig, index: int, window: int = 0,
                   kept=None, rows=None, cross: bool = False):
    """Differential attention on ``x`` -> ``(x + Op, (k1, k2, v))``: with ``a``
    the block's normed input, ``[q | k | v] = a W_qkv + b`` (``cross``: ``q = a
    W_q + b`` and the keys and values are ``kept``, an EARLIER layer's); pair
    ``j``'s half-head ``c`` is a causal softmax of ``q^c_j . k^c_{j // (P/G)}
    / sqrt(d)`` over the pair's values ``[v^1 | v^2]`` (``2d`` wide): TWO calls
    of the batched causal kernel a layer (``c`` = 1, 2) at ``d`` and ``2d``,
    under a ``window`` the band's; then :func:`_differ` and ``W_o + b_o``.
    No rotary. With ``rows`` (positions in a sequence, static) the QUERY side
    runs on those rows alone: where ``x`` is still every row (``kept`` None:
    the layer that makes the keys and values) it makes them on all rows and
    cuts ``a`` and ``x`` to the rows; where ``x`` is the rows already
    (``cross``) nothing is cut. B x R single-row queries against S keys are
    plain products (:func:`_row_attention`): decode-shaped, with no cache to
    manage, since the keys die with the step. Under the scopes ``proj``,
    ``window_attn`` / ``sparse_attn`` / ``cross_attn`` (the two calls) and
    ``diff`` (the difference, its norm and scale)."""
    g = cfg.num_kv_heads // 2
    with jax.named_scope("proj"):
        x, q1, q2, *made = jax.jit(_diff_inputs, static_argnums=(2, 3, 4, 5))(
            {name: u for name, u in p.items() if name in _DIFF_INPUTS}, x, cfg, batch,
            None if rows is None or cross else tuple(rows), cross)
    k1, k2, v = kept if cross else made
    s_q, s_k = x.shape[0] // batch, v.shape[0] // batch

    def seqs(u, s):
        return u.reshape(batch, s, -1)

    with jax.named_scope("cross_attn" if cross else "window_attn" if window else "sparse_attn"):
        if rows is not None:
            o1, o2 = (jax.jit(_row_attention, static_argnums=(3, 4))(
                seqs(q, s_q), seqs(k, s_k), seqs(v, s_k), tuple(rows), g).reshape(x.shape[0], -1)
                for q, k in ((q1, k1), (q2, k2)))
        else:
            attend, band = sa.masked_gqa_attention, {}
            if window:  # the same kernel under its own name
                attend, band = sa.windowed_gqa_attention, {"window": window}
            attend = jax.jit(attend, static_argnames=("num_kv_heads", "block_q", "block_k", "window"))
            o1, o2 = (attend(seqs(q, s_q), seqs(k, s_k), seqs(v, s_k), num_kv_heads=g,
                             block_q=cfg.causal_q_tile, block_k=cfg.causal_kv_tile,
                             **band).reshape(x.shape[0], -1) for q, k in ((q1, k1), (q2, k2)))
    with jax.named_scope("diff"):
        o = jax.jit(_differ, static_argnums=(3, 4))(
            o1, o2, {name: u for name, u in p.items() if name in _DIFFER_INPUTS}, cfg, index)
    with jax.named_scope("proj"):
        x = jax.jit(_onto_biased)(x, o, p["wo"], p.get("b_o"))
    return x, (k1, k2, v)


_DIFF_INPUTS = ("norm1", "norm1_b", "w_qkv", "b_qkv", "w_q", "b_q")  # what _diff_inputs reads of a layer
_DIFFER_INPUTS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "sub_norm")  # and _differ


def _diff_inputs(p, x, cfg: DecoderConfig, batch: int, rows, cross: bool):
    """``(x, q1, q2)`` and, unless ``cross``, ``(k1, k2, v)`` after them, from
    the block's normed input: keys and values on every row, and with ``rows``
    (static positions in each of the ``batch`` sequences) the queries, and
    ``x`` itself, on those rows alone. Jitted by name: the layers of a model
    trace once a kind."""
    a = block_norm(x, p, "norm1", cfg).astype(p["w_q" if cross else "w_qkv"].dtype)
    made = () if cross else _diff_keys_values(p, a, cfg)
    if rows is not None:
        a, x = (u.reshape(batch, -1, u.shape[-1])[:, np.asarray(rows)].reshape(-1, u.shape[-1])
                for u in (a, x))
    return (x, *_diff_queries(p, a, cfg), *made)


def _onto_biased(x, o, wo, bo):
    """``x + (o W_o + b_o)`` (``b_o`` None: no bias)."""
    out = _mm(o, wo)
    return x + (out if bo is None else out + bo.astype(jnp.float32)).astype(x.dtype)


def _mlp_onto(p, x, cfg: DecoderConfig):
    """``x + residual_multiplier * MLP(rms(x))``, the dense feed-forward of
    a model with a residual multiplier, jitted by name (as :func:`state_space`'s parts)."""
    dt = p["w_up"].dtype
    b = rms_norm(x, p["norm2"], cfg.rms_eps).astype(dt)
    h = hidden_rows(lambda w: _mm(b, w), p.get("w_gate"), p["w_up"]).astype(dt)
    return _onto(x, h, p["w_down"], cfg.residual_multiplier)


def _onto_normed(x, o, wo, g, eps: float):
    """``x + rms(o W_o; g)``: a sandwich layer's attention branch, normed
    again before it is added. The norm is the product's epilogue (float32,
    no pass of its own over ``[T, D]``); jitted by name, as
    :func:`state_space`'s parts: the layers of a model trace and lower once."""
    return (x + rms_norm(_mm(o, wo), g, eps)).astype(x.dtype)


def _mlp_onto_normed(p, x, eps: float):
    """``x + rms(MLP(rms(x; norm2)); norm2_post)``: a sandwich layer's dense
    feed-forward (``MLP(x)`` itself where the block has no ``norm2``: the
    reordered norm), jitted by name (as :func:`_onto_normed`)."""
    dt = p["w_up"].dtype
    b = (rms_norm(x, p["norm2"], eps) if "norm2" in p else x).astype(dt)
    h = hidden_rows(lambda w: _mm(b, w), p.get("w_gate"), p["w_up"]).astype(dt)
    return _onto_normed(x, h, p["w_down"], p["norm2_post"], eps)


def _attention(p, x, angles, idx_angles, batch: int, cfg: DecoderConfig, window: int = 0):
    """The attention operator on ``x [B*S, D]`` -> ``(x + Op, live,
    causal)``, the last two the layer's statistics tiles. Each part is a
    call of its own under its scope (``proj``, ``indexer``,
    ``sparse_attn``): a scope reaches the chip's profile only on ops
    inlined from a call. Under a ``window`` (a SLIDING layer: keys ``t -
    window < j <= t``) the same kernel takes ONE step a query tile against
    the key window that follows the band (PR 77), under a name and a scope
    of its own (``windowed_gqa_attention``, ``window_attn``), and ``live``
    counts the statistics tiles the band meets. Where the rotary is the kernel's (:func:`_kernel_turns`: heads of
    whole lane blocks, no selection) q and k reach it float32 and unturned,
    as ``W_q``'s and ``W_k``'s products wrote them, with the layer type's two
    tables (:func:`turn_tables`, made under ``proj``: the step's ONE pair a
    type), the turned width and the two scales, and the kernel turns, scales
    and rounds a query tile once and a key tile where it meets it (PR 63).
    Where the output is gated (``attn_gate``) each head's output
    meets its own sigmoid scalar where the batched kernel writes it
    (``out_gate``: :func:`gated`'s arithmetic to the bit, and ``W_o`` reads
    what the kernel wrote; PR 58), under a selection in ``W_o``'s operand."""
    s = x.shape[0] // batch
    with jax.named_scope("proj"):
        a, q, k, v, *gate = jax.jit(_projections, static_argnums=(3, 4))(
            p, x, angles, cfg, *((True,) if window else ()))
    # a selection of blocks is a LONG sequence's: one of at most dense_len tokens attends densely
    blocks = cfg.block_select if cfg.block_select and cfg.block_select.selects(s) else None
    if cfg.indexer_heads:
        if batch != 1:
            raise ValueError(f"a learned key selection is per sequence: batch {batch} is not 1")
        with jax.named_scope("indexer"):
            mask, flags = jax.jit(_indexer, static_argnums=3)(p, a, idx_angles, cfg)
            live, causal = sa.live_tiles(flags, s)
        with jax.named_scope("sparse_attn"):
            o = jax.jit(sa.masked_gqa_attention, static_argnames=("num_kv_heads", "block_q"))(
                *(u[None] for u in (q, k, v)), mask, num_kv_heads=cfg.num_kv_heads,
                block_q=max(cfg.attn_q_tile, mask.shape[2]))[0]
    elif blocks is not None:  # no indexer: the attention's own q and k select, a key head at a time
        if batch != 1:
            raise ValueError(f"a selection of blocks is per sequence: batch {batch} is not 1")
        with jax.named_scope("block_select"):  # pooling, scores, search: a call of its own
            flags, pieces = jax.jit(sa.select_blocks, static_argnames=(
                "num_kv_heads", "selection", "block_q"))(
                q, k, num_kv_heads=cfg.num_kv_heads, selection=blocks, block_q=cfg.q_tile)
            counted = [sa.live_tiles(pieces[g], s) for g in range(cfg.num_kv_heads)]
            live, causal = sum(l for l, _ in counted), sum(c for _, c in counted)  # over the key heads
        with jax.named_scope("sparse_attn"):
            o = jax.jit(sa.masked_gqa_attention, static_argnames=(
                "num_kv_heads", "block_q", "mask_blocks"))(
                *(u[None] for u in (q, k, v)), flags, num_kv_heads=cfg.num_kv_heads,
                block_q=cfg.attn_q_tile, mask_blocks=blocks)[0]
    else:
        live = causal = batch * sa.causal_tile_count(s)  # every earlier key is attended
        attend, band = sa.masked_gqa_attention, {}
        if window:  # the same kernel under its own name (a trace tells the two kinds of layer apart)
            attend, band = sa.windowed_gqa_attention, {"window": window}
            live = batch * sa.band_tile_count(s, window)
        if gate and cfg.attn_gate == "head_wise":  # applied where the kernel writes o: nothing is
            band["out_gate"] = gate.pop().reshape(batch, s, -1)  # left for `gated` below
        if _kernel_turns(cfg, angles):  # q and k came float32 and unturned: the kernel's to turn
            with jax.named_scope("proj"):  # every layer's of a type: the step's ONE pair
                band["turn"] = jax.jit(turn_tables, static_argnums=1)(angles, cfg.head_dim)
            band["q_scale"], band["turn_scale"] = _rotary_scales(cfg, bool(window))
            band["turn_width"] = 2 * angles.shape[-1]
        with jax.named_scope("window_attn" if window else "sparse_attn"):
            o = jax.jit(attend, static_argnames=("num_kv_heads", "block_q", "block_k", "window",
                                                 "turn_width", "turn_scale", "q_scale"))(
                *(u.reshape(batch, s, -1) for u in (q, k, v)), num_kv_heads=cfg.num_kv_heads,
                block_q=cfg.causal_q_tile, block_k=cfg.causal_kv_tile,
                **band).reshape(x.shape[0], -1)
    with jax.named_scope("proj"):
        if gate and cfg.attn_gate == "elementwise":
            x = jax.jit(_gated_onto, static_argnums=4)(x, o, gate[0], p["wo"], cfg.residual_multiplier)
        elif gate:
            x = jax.jit(gated)(x, o, gate[0], p["wo"])
        elif cfg.sandwich:
            x = jax.jit(_onto_normed, static_argnums=4)(x, o, p["wo"], p["norm1_post"], cfg.rms_eps)
        elif cfg.residual_multiplier != 1.0:
            x = jax.jit(_onto, static_argnums=3)(x, o, p["wo"], cfg.residual_multiplier)
        else:
            x = jax.jit(lambda x, o, wo: x + _mm(o, wo).astype(x.dtype))(x, o, p["wo"])
    return x, live, causal


def _mlp_normed(p, x, cfg: DecoderConfig):
    """``x + MLP(norm(x))`` under the block's own norm (:func:`block_norm`),
    jitted by name (as :func:`state_space`'s parts)."""
    return x + _dense_mlp(p, block_norm(x, p, "norm2", cfg).astype(p["w_up"].dtype))


def decoder_layer(p, x, angles, idx_angles, cfg: DecoderConfig, kind, batch: int = 1, *,
                  index: int = 0, handed=None, rows=None):
    """One block: ``x [B*S, D]`` -> ``(x, stats float32)``, the first four
    of :data:`STEP_STATS` and, from a holder of a share of the experts,
    :data:`SHARE_STATS` (under a selection over latent attention those
    and :data:`PAIR_STATS`, with linear layers :data:`LINEAR_STATS` after
    them, and last :data:`AHEAD_STATS` where a pass goes ahead of the held
    rows' loop: ``cfg.layer_stats`` in all). ``kind`` is ``cfg.layer_kind(i)``: the operator
    runs under ``conv``, linear attention's, the state-space layer's, latent attention's or
    attention's scopes, the
    feed-forward under ``moe`` (the routed experts), ``shared_expert``
    (beside them, added once) or ``mlp`` (dense). What the kind does not name
    does not run: a layer that is ONE block (``cfg.single_block``) has no
    operator (``op`` None) or no feed-forward (``experts`` None), one norm,
    and counts zeros where a layer of the other kind counts. ``index`` is the
    layer's place (differential attention's ``lambda_init`` is a function of
    it). Where layers read what an EARLIER layer made (``cfg.hands_on``) the
    stack hands it on beside ``x``: ``handed`` (a mapping: ``"kv"``, the kept
    keys and values; ``"m"``, the kept scan output) comes in, and ``(x, stats,
    handed)`` goes out, with what THIS layer made where a later one reads it.
    With ``rows`` (static positions in a sequence: :func:`trunk`) a layer's
    query side runs on those rows alone (:func:`diff_attention`)."""
    op, experts = kind
    handing = handed is not None
    handed = dict(handed or {})
    live, causal = 0, 0
    # under hyper-connections x is the STREAMS [T, n*D]: a branch reads a mix of them (`mixed_in`: what
    # it reads, and the mixing numbers for the way back) and hands back its OUTPUT alone, which
    # `mixed_out` lays onto another mix
    plain = not cfg.hc_mult

    def mixed_in(x, which):
        with jax.named_scope("hyper_in"):
            return hc.hyper_in(x, p[which + "_phi"], p[which + "_alpha"], p[which + "_b"],
                               streams=cfg.hc_mult, iters=cfg.hc_iters, eps=cfg.hc_eps,
                               norm_eps=cfg.rms_eps, clamp=cfg.hc_clamp)

    def mixed_out(x, y, mix):
        with jax.named_scope("hyper_out"):
            handed["defect"] = jnp.maximum(handed.get("defect", 0.0), hc.sum_defect(mix, cfg.hc_mult))
            return hc.hyper_out(x, y, mix, streams=cfg.hc_mult)

    if cfg.sandwich and (op not in (ATTENTION, LINEAR) or experts or cfg.attn_gate
                         or cfg.indexer_heads or cfg.residual_multiplier != 1.0):
        raise ValueError("a norm AFTER a branch (sandwich; with none before it, the reordered norm) "
                         "is built on plain causal attention, linear attention and a dense MLP alone")
    if not (cfg.pre_norm or cfg.sandwich):
        raise ValueError("a block whose branches have no norm, before or after, is not built")
    if op == CONV:
        with jax.named_scope("conv"):
            x = jax.jit(gated_short_conv, static_argnums=(2, 3))(p, x, batch, cfg)
    elif op == LINEAR and cfg.linear_decay == "fixed":  # (its rotary's angles, where it has one)
        x = fixed_decay_attention(p, x, angles if cfg.linear_rotary else None, batch, cfg)
    elif op == LINEAR:
        x = linear_attention(p, x, batch, cfg)
    elif op == MAMBA:
        x = state_space(p, x, batch, cfg)
    elif op == MAMBA1:
        if index in cfg.hands_on:  # its scan output (before the gate) is read later
            x, handed["m"] = selective_state_space(p, x, batch, cfg, keep=True)
        else:
            x = selective_state_space(p, x, batch, cfg)
    elif op == GMU:
        x = gated_memory(p, x, handed["m"], cfg)
    elif cfg.diff_attention and op in (ATTENTION, SLIDING, CROSS):
        x, made = diff_attention(p, x, batch, cfg, index, cfg.sliding_window if op == SLIDING else 0,
                                 kept=handed.get("kv"), rows=rows, cross=op == CROSS)
        if index in cfg.hands_on:
            handed["kv"] = made
        if rows is None:  # the statistics tiles of the kernel's calls (single rows run none)
            s = x.shape[0] // batch
            causal = batch * sa.causal_tile_count(s)
            live = batch * sa.band_tile_count(s, cfg.sliding_window) if op == SLIDING else causal
    elif op == LATENT and not plain:
        u, mix = mixed_in(x, "hc1")
        y, live, causal = latent_attention(p, u, angles, batch, cfg, onto=False)
        x = mixed_out(x, y, mix)
    elif op == LATENT:
        x, live, causal = latent_attention(p, x, angles, batch, cfg, idx_angles)
    elif op is not None:  # (None: a feed-forward alone)
        x, live, causal = _attention(p, x, angles, idx_angles, batch, cfg,
                                     cfg.sliding_window if op == SLIDING else 0)
    share = experts and cfg.layer_stats > 4
    streams = x  # (under hyper-connections: what the feed-forward's output goes onto a mix of)
    if not plain:
        if op != LATENT or experts is None or cfg.sandwich or cfg.norm == "layer":
            raise ValueError("hyper-connections are built around latent attention and a gated MLP or "
                             "the experts, each under its own RMS norm, alone")
        x, mix = mixed_in(x, "hc2")
    given = ()  # from the shared expert: the layer's normed rows b, and x + Shared(b)
    if experts and cfg.shared_experts:
        with jax.named_scope("shared_expert"):  # every token's, whatever it chose; the norm is here, once
            def beside(p, x):
                b = rms_norm(x, p["norm2"], cfg.rms_eps).astype(x.dtype)
                return b, x + _dense_mlp(p, b) if plain else _dense_mlp(p, b)

            given = jax.jit(beside)({"norm2": p["norm2"], "w_up": p["shared_up"],
                                     "w_down": p["shared_down"],
                                     **({"w_gate": p["shared_gate"]} if "shared_gate" in p else {})}, x)
    with jax.named_scope("moe" if experts else "mlp"):
        def mlp(p, x, *given):
            b, onto = given or (rms_norm(x, p["norm2"], cfg.rms_eps).astype(x.dtype),
                                x if plain else 0)  # (the BRANCH's output alone where streams mix)
            if not experts:
                return (x + _dense_mlp(p, b) if plain else _dense_mlp(p, b)), jnp.zeros((), jnp.int32)
            y, tokens = dropless_moe(
                b, p["router"], p.get("w_gate"), p["w_up"], p["w_down"], k=cfg.experts_per_token,
                num_experts=cfg.num_experts, experts_held=cfg.experts_held,
                renormalise=cfg.norm_topk_prob, scoring=cfg.router_scoring,
                select_bias=p.get("router_bias"), gate_eps=cfg.gate_eps,
                gate_scale=cfg.routed_scaling_factor, groups=cfg.router_groups,
                groups_kept=cfg.router_groups_kept)
            out = onto + y
            return (out, jnp.max(tokens), jnp.sum(tokens)) if share else (out, jnp.max(tokens))

        if experts is None:  # an operator ALONE: no feed-forward, and nothing of one is counted
            busiest, held = jnp.zeros((), jnp.int32), ()
        elif cfg.sandwich:
            dense = {k: p[k] for k in ("norm2", "w_gate", "w_up", "w_down", "norm2_post") if k in p}
            x = jax.jit(_mlp_onto_normed, static_argnums=2)(dense, x, cfg.rms_eps)
            busiest, held = jnp.zeros((), jnp.int32), ()
        elif not experts and cfg.residual_multiplier != 1.0:
            dense = {k: p[k] for k in ("norm2", "w_gate", "w_up", "w_down") if k in p}
            x = jax.jit(_mlp_onto, static_argnums=2)(dense, x, cfg)
            busiest, held = jnp.zeros((), jnp.int32), ()
        elif not experts and cfg.norm == "layer":
            dense = {k: p[k] for k in ("norm2", "norm2_b", "w_gate", "w_up", "w_down") if k in p}
            x = jax.jit(_mlp_normed, static_argnums=2)(dense, x, cfg)
            busiest, held = jnp.zeros((), jnp.int32), ()
        else:
            x, busiest, *held = jax.jit(mlp)(p, x, *given)
    if not plain:
        x = mixed_out(streams, x, mix)
    even = x.shape[0] * cfg.experts_per_token / cfg.num_experts if experts else 0.0
    stats = [busiest.astype(jnp.float32), jnp.float32(even),
             jnp.asarray(live, jnp.float32), jnp.float32(causal)]
    if cfg.layer_stats > 4:
        routed = x.shape[0] * cfg.experts_per_token if experts else 0
        stats += [held[0].astype(jnp.float32) if held else jnp.float32(0), jnp.float32(routed)]
    if cfg.counts_pairs:
        # a selection keeps min(t + 1, topk) of a query's keys, a band min(t + 1, window): the sum
        # over a sequence's queries, in the layers that select or slide
        s = x.shape[0] // batch
        if cfg.block_select:  # a key head's blocks, the diagonal one cut at the query: over the key
            # heads of a layer that selects (all of a short sequence's pairs: it attends densely)
            through = batch * cfg.num_kv_heads * (op == ATTENTION)
            selected = cfg.block_select.pairs(s) if cfg.block_select.selects(s) else s * (s + 1) // 2
        else:
            width = cfg.topk if cfg.selects_over_latent else cfg.sliding_window
            kept = min(s, width)
            through = batch * (cfg.selects_over_latent or op == SLIDING)
            selected = kept * (kept + 1) // 2 + (s - kept) * width
        stats += [jnp.float32(through * selected), jnp.float32(through * (s * (s + 1) // 2))]
    if cfg.has_linear:
        s = x.shape[0] // batch
        if not cfg.counts_pairs:  # PAIR_STATS' places: nothing selects
            stats += [jnp.float32(0), jnp.float32(0)]
        chunks = 0  # the layer's kernel's head-sequences x its chunks: the delta rule's, or the scan's
        if op == LINEAR and cfg.linear_decay == "fixed":
            chunks = batch * cfg.num_heads * (s // lightning.step_rows(s, cfg.linear_chunk)[1])
        elif op == LINEAR:
            chunks = batch * cfg.num_heads * (s // chunk_rows(s, cfg.linear_chunk))
        elif op == MAMBA:
            chunks = batch * cfg.ssm_heads * (s // scan_rows(s))
        elif op == MAMBA1:  # no heads: the kernel's grid steps, channel tiles x chunks a sequence
            tile = scan_tiles(s, cfg.scan_channels)
            chunks = batch * (cfg.scan_channels // tile[1]) * (s // tile[0])
        stats += [jnp.float32(x.shape[0] if chunks else 0), jnp.float32(chunks)]
    if cfg.rows_go_ahead:
        # the places of the groups the step has not
        stats += [jnp.float32(0)] * (cfg.layer_stats - len(AHEAD_STATS) - len(stats))
        ahead = rows_ahead(x.shape[0] * cfg.experts_per_token, cfg.experts_held[1], cfg.num_experts)
        stats += [jnp.minimum(held[0], ahead).astype(jnp.float32) if held else jnp.float32(0)]
    return (x, jnp.stack(stats), handed) if handing else (x, jnp.stack(stats))


def causal_call_steps(cfg: DecoderConfig, i: int, batch: int, s: int) -> Tuple[int, int, int]:
    """Layer ``i``'s share of :data:`BLOCK_STATS` and :data:`PART_STATS`: the
    head tiles its call of the batched causal kernel visits, the grid steps
    it takes and the part tiles those steps write
    (``sparse_attention.causal_steps``, from what the call is given: latent
    attention's heads alone in their groups under the selection's mask or
    none, a grouped-query layer's under a selection of keys or of blocks,
    with or without a window and the kernel's own rotary), constants of the
    shapes; zeros where it makes no such call. The first two differ only
    where a grid step takes a block of heads, the last two only where a
    stacked group's rows are cut into parts."""
    op = cfg.layer_kind(i)[0]
    tiles = {"block_q": cfg.causal_q_tile, "block_k": cfg.causal_kv_tile}
    if op == LATENT:
        if cfg.indexer_heads:  # the tiles `_indexer` has the selection's mask written in
            tiles["mask_tiles"] = (sa.pick_tile(s, cfg.q_tile),
                                   sa.mask_tile(s, sa.pick_tile(s, cfg.kv_tile)))
        return sa.causal_steps(batch, s, cfg.num_heads, 1, cfg.qk_nope_head_dim, cfg.v_head_dim,
                               cfg.qk_rope_head_dim, **tiles)
    if cfg.diff_attention:  # two calls a layer, pairs of half-heads over values twice as wide
        if op not in (ATTENTION, SLIDING, CROSS):
            return 0, 0, 0
        one = sa.causal_steps(batch, s, cfg.num_kv_heads // 2, cfg.num_heads // cfg.num_kv_heads,
                              cfg.head_dim, 2 * cfg.head_dim,
                              window=cfg.sliding_window if op == SLIDING else None, **tiles)
        return tuple(2 * count for count in one)
    if op not in (ATTENTION, SLIDING):
        return 0, 0, 0
    group = (batch, s, cfg.num_kv_heads, cfg.heads(i) // cfg.num_kv_heads, cfg.head_dim, cfg.head_dim)
    if cfg.indexer_heads:  # a selection of keys over grouped-query heads, as `_attention` calls it
        q_tile = sa.pick_tile(s, cfg.q_tile)
        return sa.causal_steps(*group, block_q=max(cfg.attn_q_tile, q_tile), mask_tiles=(
            q_tile, sa.mask_tile(s, sa.pick_tile(s, cfg.kv_tile))))
    if cfg.block_select and cfg.block_select.selects(s):  # of blocks: the flags' own key tile
        return sa.causal_steps(*group, block_q=cfg.attn_q_tile, mask_tiles=(
            sa.pick_tile(s, 128), cfg.block_select.tiles(s)[0]))
    return sa.causal_steps(*group, window=cfg.sliding_window if op == SLIDING else None,
                           turned=_kernel_turns(cfg, cfg.rotary or None), **tiles)


def trunk(params, x, pos, cfg: DecoderConfig, batch: int = 1, exits: bool = False, rows=None):
    """``x [B*S, D]``, the embedded tokens of ``batch`` sequences one after
    the other, each at ``pos`` (static: ``[S, 3]`` under the multimodal
    rotary, else ``[S]``), through every layer -> ``(x [B*S, D], stats
    in :data:`STEP_STATS`' order``, then :data:`SHARE_STATS` where the
    holder has a share of the experts, :data:`PAIR_STATS` under a
    selection over latent attention, :data:`LINEAR_STATS` after both
    where layers are linear, :data:`AHEAD_STATS` last where a pass goes
    ahead of the held rows' loop). A looped model (``cfg.passes > 1``) goes
    through the stack that many times (:func:`_passes`): ``x`` is then the
    last pass's NORMED rows, :data:`LOOP_STATS` follow every other group,
    and with ``exits`` the exit distribution ``p [R, B*S]`` at every token
    (:func:`exit_distribution`) comes back third. ``rows`` (static positions
    in a sequence; ``None``: all) are the rows WANTED: ``x`` comes back at those
    rows alone, ``[B * len(rows), D]``, and where the schedule ends in layers
    that mix no tokens (``cfg.cut_layer``) those layers, and the query side of
    the layer whose keys and values they read, RUN on those rows alone: the
    same layers' functions at another row count, and :data:`ROWS_STATS` after
    every other group."""
    s = x.shape[0] // batch
    # (under rope_parameters: the FULL layers' table, over the part of a head their rotary turns)
    angles = rotary_angles(pos, cfg.rope_theta, cfg.rope_dim // 2, cfg.mrope_section,
                           cfg.rope_yarn) if cfg.rotary else None  # None: nothing is turned
    idx_angles = None
    if cfg.indexer_heads:
        # the indexer's vectors turn with the sequence index alone (under YaRN by its frequencies:
        # where the rotary part is as wide as the key's, by the rotary key's own angles)
        idx_angles = rotary_angles(
            np.arange(s), cfg.rope_theta, (cfg.indexer_rope_dim or cfg.indexer_head_dim) // 2,
            yarn=cfg.rope_yarn)
    # the rotary is the layer type's: a second table where SLIDING layers have their own
    by_op = {SLIDING: rotary_angles(pos, cfg.sliding_rope_theta or cfg.rope_theta,
                                    cfg.head_dim // 2)} if cfg.has_window and cfg.rotary else {}
    if cfg.linear_rotary:  # the LINEAR layers' own: plain, over the whole of their heads
        by_op[LINEAR] = rotary_angles(pos, cfg.rope_theta, cfg.linear_head_dim // 2)
    if batch != 1:
        angles = angles if angles is None else jnp.tile(angles, (batch, 1))
        by_op = {op: jnp.tile(table, (batch, 1)) for op, table in by_op.items()}
    if cfg.stream_dtype:
        x = x.astype(cfg.stream_dtype)
    if cfg.hc_mult:  # the embedded row enters as hc_mult equal streams, side by side
        if cfg.passes > 1 or rows is not None:
            raise ValueError("hyper-connections in a looped stack, or on some rows alone, are not built")
        x = jnp.concatenate([x] * cfg.hc_mult, axis=1)
    # a step in which some causal call takes a BLOCK of heads a grid step counts BLOCK_STATS, last of
    # all: constants of the shapes, summed here over the layers (and a looped model's passes) and
    # laid down as ONE constant (a scalar a layer is a transfer a layer while the step is traced)
    cut = cfg.cut_layer if rows is not None else None  # the layer from which the wanted rows run alone
    calls = [causal_call_steps(cfg, i, batch, s) for i in range(len(params["layers"]))
             if cut is None or i < cut]  # (on the wanted rows alone a layer makes no call of the kernel)
    tiles, steps, parts = (cfg.passes * sum(column) for column in zip(*calls))
    cuts = (parts,) if parts != steps else ()  # PART_STATS, the vector's last
    blocks = (tiles, steps) if cuts or tiles != steps else ()
    tail = blocks + ((0, 0) + cuts if cuts else ())  # (ROWS_STATS' two places stand between them)
    stats = jnp.zeros((cfg.layer_stats,), jnp.float32)
    served = jnp.asarray([batch * s, batch], jnp.float32)
    at = None if rows is None else np.asarray(rows)

    def wanted(u):  # the wanted rows of each sequence
        return u.reshape(batch, s, -1)[:, at].reshape(batch * len(at), -1)

    found = {}  # under hyper-connections: the step's largest defect of an H_res

    def stack(x, stats):  # every layer once
        # what a layer made that a later one reads, beside x (under hyper-connections: the largest
        # defect of an H_res so far, which every layer raises)
        handed = {} if cfg.hands_on or cfg.hc_mult else None
        for i, p in enumerate(params["layers"]):
            kind = cfg.layer_kind(i)
            if handed is None:
                x, layer_stats = decoder_layer(p, x, by_op.get(kind[0], angles), idx_angles, cfg,
                                               kind, batch)
            else:
                x, layer_stats, handed = decoder_layer(
                    p, x, by_op.get(kind[0], angles), idx_angles, cfg, kind, batch, index=i,
                    handed=handed, rows=rows if cut is not None and i >= cut else None)
                if i == cut and "m" in handed:  # the later layers read their own rows of it
                    handed["m"] = wanted(handed["m"])
            stats = stats + layer_stats
        if cfg.hc_mult:  # the streams are SUMMED ahead of the final norm, in float32
            found["defect"] = handed["defect"]
            with jax.named_scope("hyper_out"):  # (lane-aligned slices: no [T, n, D] relayout)
                d = cfg.hidden_size
                x = sum(x[:, j * d:(j + 1) * d].astype(jnp.float32)
                        for j in range(cfg.hc_mult)).astype(x.dtype)
        return (x if rows is None or cut is not None else wanted(x)), stats

    if cfg.passes > 1:
        x, stats, logits = _passes(params, x, stack, stats, served, cfg, batch, tail)
        return (x, stats, exit_distribution(logits)) if exits else (x, stats)
    x, stats = stack(x, stats)
    if cut is not None:  # every group the step has not at 0, then the rows the layers ran
        layers = len(params["layers"])
        ran = cut * batch * s + (layers - cut) * batch * len(at)
        rest = (0,) * (LAYER_GROUPS - cfg.layer_stats + len(LOOP_STATS)) + (blocks or (0, 0))
        return x, jnp.concatenate([stats[:4], served, stats[4:], np.asarray(
            rest + (ran, layers * batch * s) + cuts, np.float32)])
    if tail:  # every group the step has not at 0, LOOP_STATS' places among them
        rest = (0,) * (LAYER_GROUPS - cfg.layer_stats + len(LOOP_STATS)) + tail
        stats = jnp.concatenate([stats[:4], served, stats[4:], np.asarray(rest, np.float32)])
    elif cfg.layer_stats > 4:
        stats = jnp.concatenate([stats[:4], served, stats[4:]])
    else:
        stats = jnp.concatenate([stats, served])
    if cfg.hc_mult:  # every group the step has not at 0, then HYPER_STATS, the vector's last
        before = len(STEP_STATS) + LAYER_GROUPS - 4 + len(LOOP_STATS + BLOCK_STATS + ROWS_STATS + PART_STATS)
        mixes = 2.0 * len(params["layers"]) * batch * s
        stats = jnp.concatenate([stats, np.zeros((before - stats.shape[0],), np.float32),
                                 jnp.stack([jnp.float32(mixes), found["defect"]])])
    return x, stats


def _pass_end(x, g, gate, eps: float):
    """The end of a pass on its rows ``x [T, D]``: ``h = rms(x; g)``, the
    rows the next pass (or the head) reads, in the stream's type, and the
    exit gate's logit ``h w_e + b_e [T]`` float32 from those rows as they
    were rounded. One pass over ``[T, D]``: the
    logit is a row sum of the norm's own output. Jitted by name."""
    h = rms_norm(x, g, eps).astype(x.dtype)
    return h, jnp.sum(h.astype(jnp.float32) * gate["w"].astype(jnp.float32), axis=-1) + gate["b"]


def exit_distribution(logits):
    """The exit gate's logits ``[R, ...]``, one a pass, -> ``p [R, ...]``
    float32: with ``lambda_r = sigmoid(logit_r)``, ``p_r = lambda_r prod_{j<r}
    (1 - lambda_j)`` for ``r < R`` and ``p_R = prod_{j<R} (1 - lambda_j)``:
    the probability that the model answers from pass ``r``; sums to 1."""
    lam = jax.nn.sigmoid(logits.astype(jnp.float32))
    stayed = jnp.concatenate([jnp.ones_like(lam[:1]), jnp.cumprod(1.0 - lam[:-1], axis=0)])
    return jnp.concatenate([lam[:-1] * stayed[:-1], stayed[-1:]])


def _passes(params, x, stack, stats, served, cfg: DecoderConfig, batch: int, blocks=()):
    """:func:`trunk` where the stack (``stack(x, stats)``: every layer once)
    is run ``cfg.passes`` times with the
    SAME weights -> ``(x, stats, logits)``: the last pass's normed rows, the
    statistics vector (the layers' places summed over passes and layers,
    ``tokens`` and ``served`` once a step, every group the step has not 0,
    :data:`LOOP_STATS` last but for ``blocks``, the step's :data:`BLOCK_STATS` where its calls
    take a block of heads, and the places after them where they cut a group's rows) and the exit gate's logits ``[R, B*S]``
    float32. The passes are a LOOP IN THE PROGRAM
    (``lax.scan`` over the pass: the compiled module is one stack long, one
    ``while`` around it), its body the stack and
    then, under the scope ``pass_end``, the final norm and the gate
    (:func:`_pass_end`); the weights are the loop's invariants, held once;
    the rotary's tables are built outside, once."""
    def one_pass(carry, _):
        x, stats = stack(*carry)
        with jax.named_scope("pass_end"):
            x, logit = jax.jit(_pass_end, static_argnums=3)(
                x, params["norm"], params["exit_gate"], cfg.rms_eps)
        return (x, stats), logit

    (x, stats), logits = jax.lax.scan(one_pass, (x, stats), None, length=cfg.passes)
    s = x.shape[0] // batch
    at_last = exit_distribution(logits[:, s - 1::s])  # [R, B]: each frame's last token
    left_at = jnp.sum(jnp.arange(1, cfg.passes + 1, dtype=jnp.float32)[:, None] * at_last)
    return x, jnp.concatenate([
        stats[:4], served, stats[4:], jnp.zeros((LAYER_GROUPS - cfg.layer_stats,), jnp.float32),
        jnp.stack([jnp.float32(cfg.passes), left_at]),
        *([np.asarray(blocks, np.float32)] if blocks else [])]), logits


def embed(params, patches, prompt_ids, scale: float = 1.0, dtype=None):
    """``patches [N, patch_dim]`` through the linear patch embedding, then
    the prompt's rows of the embedding table: ``[N + T, D]`` in ``dtype``
    (``cfg.stream_dtype``; None: the weights' type), both times ``scale`` (a
    model's ``embedding_multiplier``) in float32 where it is not 1."""
    dt = params["patch"].dtype

    def rows(r):
        return (r if scale == 1.0 else r.astype(jnp.float32) * scale).astype(dtype or dt)

    return jnp.concatenate([rows(_mm(patches.astype(dt), params["patch"])),
                            rows(jnp.take(params["embed"], prompt_ids, axis=0))])


def head_params(params) -> dict:
    """What :func:`logits_of` reads of the tree: the final gain (a LayerNorm's
    bias beside it), and the output head or, where the two are tied, the
    embedding table."""
    table = "head" if "head" in params else "embed"
    return {name: params[name] for name in ("norm", "norm_b", table) if name in params}


def logits_of(params, x, cfg: DecoderConfig):
    """Final norm and output head on rows ``x [N, D]`` -> ``[N, V]`` float32
    (over ``logits_scaling`` where the model has one); the head alone on
    the rows of a looped model, which :func:`trunk` has normed already."""
    if cfg.passes > 1:  # a looped model's rows come normed from the end of their last pass
        a = x.astype(params["norm"].dtype)
    else:
        a = block_norm(x, params, "norm", cfg).astype(params["norm"].dtype)
    if cfg.tie_embedding:
        logits = jax.lax.dot_general(a, params["embed"], (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    else:
        logits = _mm(a, params["head"])
    return logits if cfg.logits_scaling == 1.0 else logits / cfg.logits_scaling


def frame_hidden(params, calib, frames, prompt_ids, *, cfg: DecoderConfig, threshold: float,
                 exits: bool = False, last_rows: bool = False):
    """``frames [B, P, H, W]`` raw (a frame is a sequence), calibrated,
    cut into patches, embedded and each followed by the prompt, through
    the trunk -> ``(x [B*S, D] at every token, frame after frame, stats
    [6] float32 in :data:`STEP_STATS`' order)``, and with ``exits`` a looped
    model's exit distribution ``[R, B*S]`` third (:func:`trunk`). With
    ``last_rows`` each frame's LAST row alone is wanted: ``x [B, D]``
    (:func:`trunk`'s ``rows``)."""
    from psana_ray_tpu.models.vit import patchify_panels
    from psana_ray_tpu.ops import fused_calibrate

    batch, panels, height, width = frames.shape
    n_prompt = prompt_ids.shape[0]
    if cfg.mrope_section:
        pos = frame_positions(panels, height // cfg.patch, width // cfg.patch, n_prompt)
    else:  # the index within the frame's own sequence
        pos = np.arange(panels * (height // cfg.patch) * (width // cfg.patch) + n_prompt)
    with jax.named_scope("calib"):
        x = fused_calibrate(frames, *calib, threshold=threshold, out_dtype=jnp.bfloat16)
    with jax.named_scope("embed"):
        x = jax.jit(lambda p, x, ids: jnp.concatenate(
            [embed(p, frame, ids, cfg.embedding_multiplier, cfg.stream_dtype)
             for frame in patchify_panels(x, cfg.patch)]))(
            {"patch": params["patch"], "embed": params["embed"]}, x, prompt_ids)
    return trunk(params, x, pos, cfg, batch, exits, rows=(len(pos) - 1,) if last_rows else None)


def frame_step(params, calib, frames, prompt_ids, *, cfg: DecoderConfig, threshold: float):
    """The serving step: :func:`frame_hidden`, then the logits of each
    frame's next token -> ``(logits [B, V] float32, stats [6] float32)``."""
    cut = cfg.cut_layer is not None  # the trunk's later layers run on the served rows alone
    x, stats = frame_hidden(params, calib, frames, prompt_ids, cfg=cfg, threshold=threshold,
                            last_rows=cut)
    s = x.shape[0] // frames.shape[0]
    with jax.named_scope("head"):
        logits = jax.jit(lambda p, x: logits_of(p, x, cfg))(head_params(params),
                                                            x if cut else x[s - 1::s])
    return logits, stats


def fold_step_stats(metrics, stats) -> None:
    """Add one step's statistics vector (on the host or the device: six
    values, eight from a holder of a share of the experts, ten under a
    selection over latent attention or with windowed layers (the band's
    pairs in the selection's places), twelve with linear layers, thirteen
    where a pass goes ahead of the held rows' loop, fifteen from a looped
    model, seventeen where a causal call takes a block of heads a grid step,
    nineteen where the trunk's later layers ran on the served rows alone,
    twenty where a causal call cuts a stacked group's rows into parts,
    twenty-two where the stream between the layers is several rows a token)
    to the
    pipeline's counters of the same names (``PipelineMetrics.counters``:
    in ``snapshot()`` and so under ``/metrics``); a name in :data:`MAX_STATS`
    is RAISED to the step's value, not added to."""
    names = (STEP_STATS + SHARE_STATS + PAIR_STATS + LINEAR_STATS + AHEAD_STATS + LOOP_STATS
             + BLOCK_STATS + ROWS_STATS + PART_STATS + HYPER_STATS)
    for name, value in zip(names, np.asarray(stats, np.float64)):
        if name in MAX_STATS:
            metrics.raise_counter(name, float(value))
        else:
            metrics.add_counter(name, float(value))
