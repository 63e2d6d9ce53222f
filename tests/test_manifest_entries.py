"""``BENCHMARK.json`` held to RULES: what is true of any correct manifest,
whatever its entries are called, however many there are and wherever they
stand. Each rule is stated once, as a function of a :class:`Bench` (the
manifest, ``benchmark/metrics/`` and the configuration files) and a place
(a cell, a configuration, a stem) that returns what it refuses, a line an
entry and cell. It runs over the repo's own files, and over a copy broken
the way a careless PR would break it, which it must refuse by name. The
decoder cells' test files take their loaders from here.
``benchmark/check_manifest.py`` (the manifest's form, its cap, its bounds)
runs where it did: ``tests/test_phases.py``."""

import copy
import dataclasses
import functools
import importlib
import inspect
import json
import operator
import os

import pytest

from psana_ray_tpu.models import decoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SFX, HIT, PACED = "sfx_epix_saturated", "hit_epix_saturated", "sfx_epix_paced"
COUNTERS = sum((getattr(decoder, n) for n in dir(decoder) if n.endswith("_STATS")), ())
# no suffix, and yet every hit cell's: the two stages only InfeedPipeline's path has
INFEED_ONLY = {"device_put_ms", "infeed_wait_ms"}
# a manifest `source` that is not its file's (the long form beside the bare URL): a benchmark PR's
SOURCE_DIFFERS = {"keye_vl2_prefill_epix10k2m"}
# RATCHETS: what rules 6 and 7 still find, as STEMS and CELLS. Either may shrink, neither may grow.
# Stems read under several names by equal files: ONE benchmark PR folds them (ROADMAP S0), then they go
FOLD_DEBT = ["proj_ms", "mlp_ms", "sparse_attn_ms", "conv_ms", "latent_attn_ms", "indexer_ms",
             "shared_expert_ms", "moe_ms", "expert_load_peak", "held_rows_share", "ahead_rows_share",
             "attn_live_tile_share", "gmm_roofline_share", "latent_attention_roofline_share"]
# decoder cells without a share of the peak: keye has no roofline module yet, the rest wait for room
NO_STEP_MFU = {"keye_epix_saturated", "granite_epix_saturated", "ouro_epix_saturated",
               "nemotron3_epix_saturated", "olmo_hybrid_epix_saturated", "minicpm_sala_epix_saturated",
               "phi4flash_epix_saturated", "xing4_epix_saturated"}
# rule 8's table, the ONE place tests/ says which cell has which mechanism: a scope one kind of
# layer opens -> whether a configuration has that kind (ops, feeds: its layer_kind()s, unzipped)
OPENED_BY = {
    "kda": lambda c, ops, feeds: decoder.LINEAR in ops and c.linear_decay not in ("head", "fixed"),
    "gdn": lambda c, ops, feeds: decoder.LINEAR in ops and c.linear_decay == "head",
    "lightning": lambda c, ops, feeds: decoder.LINEAR in ops and c.linear_decay == "fixed",
    "ssd": lambda c, ops, feeds: decoder.MAMBA in ops,
    "selective_scan": lambda c, ops, feeds: decoder.MAMBA1 in ops,
    "diff": lambda c, ops, feeds: c.diff_attention and bool(
        {decoder.ATTENTION, decoder.SLIDING, decoder.CROSS} & ops),
    "cross_attn": lambda c, ops, feeds: decoder.CROSS in ops,
    "gmu": lambda c, ops, feeds: decoder.GMU in ops,
    "conv": lambda c, ops, feeds: bool({decoder.CONV, decoder.MAMBA, decoder.MAMBA1} & ops) or (
        decoder.LINEAR in ops and c.linear_decay != "fixed"),
    "window_attn": lambda c, ops, feeds: decoder.SLIDING in ops,
    "latent_attn": lambda c, ops, feeds: decoder.LATENT in ops,
    "sparse_attn": lambda c, ops, feeds: decoder.ATTENTION in ops,
    "indexer": lambda c, ops, feeds: c.indexer_heads > 0,
    "block_select": lambda c, ops, feeds: decoder.ATTENTION in ops and c.block_select is not None,
    "moe": lambda c, ops, feeds: True in feeds,
    "mlp": lambda c, ops, feeds: False in feeds,
    "shared_expert": lambda c, ops, feeds: True in feeds and c.shared_experts > 0,
    "pass_end": lambda c, ops, feeds: c.passes > 1,
    "hyper_in": lambda c, ops, feeds: c.hc_mult > 0,
    "hyper_out": lambda c, ops, feeds: c.hc_mult > 0,
}


@dataclasses.dataclass
class Bench:
    manifest: dict
    specs: dict  # every file of benchmark/metrics/: <name>.json -> its content
    files: dict  # a configuration's name -> its file's content, None where there is no such file

    @classmethod
    def load(cls, root=REPO):
        def read(*path):
            with open(os.path.join(root, *path), encoding="utf-8") as f:
                return json.load(f)

        manifest, metrics = read("BENCHMARK.json"), os.path.join(root, "benchmark", "metrics")
        return cls(manifest, {n[:-len(".json")]: read(metrics, n) for n in sorted(os.listdir(metrics))},
                   {c["name"]: read(c["file"]) if os.path.exists(os.path.join(root, c["file"])) else None
                    for c in manifest["configs"]})

    @property
    def entries(self):
        return self.manifest["per_layer"] + self.manifest["end_to_end"]

    @property
    def cells(self):
        return [w["name"] for w in self.manifest["workloads"]]

    @property
    def hit_cells(self):
        return next(e for e in self.manifest["end_to_end"] if e["name"] == "fps.hit")["workloads"]

    def listing(self, cell):
        """The entries ``cell`` reports: those that list it, and those that list no cell."""
        return [e for e in self.entries if cell in e.get("workloads", [cell])]

    def spec(self, entry, key):
        return self.specs.get(entry["name"], {}).get(key, {})  # (a missing file is rule 1's to say)

    @property
    def decoder_cells(self):
        return [c for c in self.hit_cells if c != HIT]

    def cell(self, name):
        return next(w for w in self.manifest["workloads"] if w["name"] == name)

    def config(self, name):
        return next(c for c in self.manifest["configs"] if c["name"] == name)

    def file(self, cell):
        return self.files[self.cell(cell)["config"]]


BENCH = Bench.load()
DECODER_CELLS = BENCH.decoder_cells


def roofline(function):
    module, fn = function.rsplit(".", 1)
    return getattr(importlib.import_module(f"benchmark.roofline.{module}"), fn)


def lookup(cfg, path):
    return functools.reduce(operator.getitem, path.split("."), cfg)


def asked(cell, **like):
    """``{name: args}`` of the entries that list ``cell`` and whose file has
    ``like`` among its ``reader`` and ``args``: a file found by what it IS."""
    found = {e["name"]: BENCH.spec(e, "args") for e in BENCH.listing(cell)
             if like.items() <= {**BENCH.spec(e, "args"), "reader": BENCH.spec(e, "reader")}.items()}
    assert found, f"no entry of {cell} has {like}"
    return found


def need(cell, function):
    """The roofline ``function`` as ``cell`` asks for it -> ``(the function,
    its arguments from the cell's configuration, once an entry that names it)``."""
    return roofline(function), [{k: lookup(BENCH.file(cell), path) for k, path in a["shape_from"].items()}
                                for a in asked(cell, function=function).values()]


def ratio_of(cell, numerator, denominator):
    """The one name under which ``cell`` reports the program's ``numerator / denominator``."""
    name, = asked(cell, reader="program_counter_ratio", numerator=numerator, denominator=denominator)
    return name


def files_and_readers(bench, cell):
    """Rule 1: every entry has its ``benchmark/metrics/<name>.json``, whose
    ``reader`` is a module under ``benchmark/readers/`` with a callable ``read``."""
    def reads(entry):
        try:
            return callable(importlib.import_module(f"benchmark.readers.{bench.spec(entry, 'reader')}").read)
        except (ImportError, AttributeError):
            return False

    return [f"{e['name']} [{cell}]: no metric file whose reader is a module with a callable read"
            for e in bench.listing(cell) if not reads(e)]


def files_without_an_entry(bench):
    """Rule 1, the other way round."""
    return sorted(set(bench.specs) - {e["name"] for e in bench.entries})


def suffix_and_moves(bench, cell):
    """Rule 2: the cell reports what the entry ``moves``; a name's suffix says
    whom the entry may list; ``layer`` is ``kernels`` exactly on a roofline share."""
    reports, bad = {e["name"]: e.get("workloads", [cell]) for e in bench.manifest["end_to_end"]}, []
    for e in bench.listing(cell):
        if "moves" not in e:
            continue  # (an end-to-end metric)
        name, lists, moves = e["name"], e.get("workloads"), e["moves"]
        suffix = name.partition(".")[2]
        owners = [c for c in bench.cells if c.startswith(suffix + "_")]
        ok = (lists == [PACED] if suffix == "paced"
              else moves == "fps.hit" if suffix == "hit" or name in INFEED_ONLY
              else lists == owners and len(owners) == 1 and moves == "fps.hit" if suffix
              else lists in (None, [SFX]))
        bad += [f"{name} [{cell}]: lists {lists} and moves {moves}: not what its suffix says"] * (not ok)
        if cell not in reports.get(moves, ()):
            bad.append(f"{name} [{cell}]: moves {moves}, which the cell does not report")
        if (e["layer"] == "kernels") != ("roofline_share" in name):
            bad.append(f"{name} [{cell}]: layer {e['layer']!r}: kernels is a roofline share's, and only its")
    return bad


def order_not_place(bench, cell):
    """Rule 3: a ``workloads`` list follows the manifest's order of cells; the
    cell names a configuration the manifest has, and runs on one chip (a
    four-chip cell says why HERE)."""
    at = {c: i for i, c in enumerate(bench.cells)}
    bad = [f"{e['name']} [{cell}]: workloads {e['workloads']} are not in the manifest's order"
           for e in bench.listing(cell) if "workloads" in e
           and [at.get(c) for c in e["workloads"]] != sorted({at[c] for c in e["workloads"] if c in at})]
    runs = bench.cell(cell)
    return bad + [f"[{cell}]: configuration {runs['config']!r} on {runs['chips']} chips"] * (
        runs["config"] not in bench.files or runs["chips"] != 1)


def configuration_agrees_with_its_file(bench, name):
    """Rule 3, a configuration at a time: its ``file`` exists and says the same
    ``reduced`` and (but for :data:`SOURCE_DIFFERS`) the same ``source``."""
    entry, file = bench.config(name), bench.files[name] or {}
    keys = ["reduced"] + ["source"] * (name not in SOURCE_DIFFERS)
    return [f"{name}: {k} {entry[k]!r} is not its file's ({entry['file']}) {file.get(k)!r}"
            for k in keys if entry[k] != file.get(k)]


def fits_the_cell(bench, cell):
    """Rule 4, for EVERY cell an entry lists: its ``@names`` are the cell's
    ``trace_names``; its ``shape_from`` reads keys the configuration has; its
    ``function`` is under ``benchmark/roofline/`` and takes exactly what it is
    given; what it reads of a decoder's counters, the step counts."""
    def takes(function, shape_from, share=None, **_):
        try:
            args = inspect.signature(roofline(function)).parameters.values()
            [lookup(cfg, path) for path in shape_from.values()]
        except (ImportError, AttributeError, KeyError, TypeError):
            return False
        given = set(shape_from) | ({"held_share"} if share else set())
        return {a.name for a in args if a.default is a.empty} <= given <= {a.name for a in args}

    cfg, bad = bench.file(cell), []
    for e in bench.listing(cell):
        spec, say = bench.spec(e, "args"), f"{e['name']} [{cell}]: "
        bad += [f"{say}{k} {v} is not among the configuration's trace_names" for k, v in spec.items()
                if isinstance(v, str) and v.startswith("@") and v[1:] not in cfg["trace_names"]]
        if "function" in spec and not takes(**spec):
            bad.append(f"{say}no {spec['function']} under benchmark/roofline/ takes {spec['shape_from']} "
                       f"of the configuration's keys")
        if cell in bench.decoder_cells and bench.spec(e, "reader") != "counter_ratio":  # (the harness's own)
            bad += [f"{say}{key} {part[key]!r} is no counter of the step"
                    for part in (spec, spec.get("share", {}), spec.get("share_where_alone", {}))
                    for key in ("numerator", "denominator") if part.get(key, COUNTERS[0]) not in COUNTERS]
    return bad


def host_path_is_every_hit_cell_s(bench, cell):
    """Rule 5: an entry the hit cell shares with a decoder cell, every cell of
    ``fps.hit`` reports (``calib_roofline_share.hit`` lists the hit cell alone
    and is not such an entry: PERF.md section 7 (1a)); order is rule 3's."""
    shared = [e for e in bench.manifest["per_layer"] if {HIT} < set(e.get("workloads", ()))]
    return [f"{e['name']} [{cell}]: the host path's, and does not list the cell"
            for e in shared if cell not in e["workloads"]] + ["no entry is the host path's"] * (not shared)


def one_reading_one_name(bench, stem):
    """Rule 6, a RATCHET: among entries of one stem and one ``moves`` no two
    files have equal ``reader`` and ``args`` -> what is refused under ``stem``
    (under every stem outside :data:`FOLD_DEBT`, where ``stem`` is none of it):
    a copy outside the debt and, whatever the stem, a ``.hit`` entry beside a
    cell's own (a stem is folded whole or not at all)."""
    groups, bad = {}, []
    for e in bench.manifest["per_layer"]:
        reads = json.dumps([bench.spec(e, "reader"), bench.spec(e, "args")], sort_keys=True)
        groups.setdefault((e["name"].partition(".")[0], e["moves"], reads), []).append(e["name"])
    for (s, *_), names in groups.items():
        if len(names) > 1 and (s == stem if stem in FOLD_DEBT else s not in FOLD_DEBT):
            bad += [f"{names} read ONE reading under {len(names)} names"] * (s not in FOLD_DEBT)
            bad += [f"{names}: a stem half folded"] * any(n.endswith(".hit") for n in names)
    return bad


def a_share_of_the_peak(bench, cell):
    """Rule 7, a RATCHET: a decoder cell reports its step's share of the peak
    FLOP/s, or stands in :data:`NO_STEP_MFU`."""
    has = any(bench.spec(e, "reader") == "peak_flops_share" for e in bench.listing(cell))
    return [] if has or cell in NO_STEP_MFU else [f"[{cell}]: no entry read by peak_flops_share lists the cell"]


def scope_where_a_layer_opens_it(bench, cell):
    """Rule 8: an entry whose ``scope`` one kind of layer opens lists only
    cells whose configuration has a layer of that kind (on the chip a scope
    the step lacks reads null, and a null refuses a benchmark PR)."""
    cfg = decoder.DecoderConfig.from_mapping(bench.file(cell))
    ops, feeds = map(set, zip(*(cfg.layer_kind(i) for i in range(cfg.num_layers))))
    return [f"{e['name']} [{cell}]: no layer of the cell's configuration opens the scope {scope!r}"
            for e in bench.listing(cell) for scope in [bench.spec(e, "args").get("scope")]
            if scope in OPENED_BY and not OPENED_BY[scope](cfg, ops, feeds)]


# over the repo's own files: a case a rule and a cell, a configuration or a stem -- never an entry
RULES = [(files_and_readers, BENCH.cells), (suffix_and_moves, BENCH.cells), (order_not_place, BENCH.cells),
         (fits_the_cell, BENCH.cells), (host_path_is_every_hit_cell_s, BENCH.hit_cells),
         (a_share_of_the_peak, DECODER_CELLS), (scope_where_a_layer_opens_it, DECODER_CELLS),
         (configuration_agrees_with_its_file, [c["name"] for c in BENCH.manifest["configs"]]),
         (one_reading_one_name, FOLD_DEBT + ["every_other_stem"])]


@pytest.mark.parametrize("rule,at", [pytest.param(rule, at, id=f"{rule.__name__}-{at}")
                                     for rule, places in RULES for at in places])
def test_the_manifest_keeps_the_rule(rule, at):
    assert not rule(BENCH, at)


def test_no_metric_file_is_without_an_entry():
    assert not files_without_an_entry(BENCH)


# and each rule over a copy a careless PR broke: it refuses, and names the entry and the cell

def put(bench, name, like, workloads, **args):
    """Enter ``name`` (in its place, if it stands) as the first entry whose file has ``like``,
    with that file but for ``args``, listing ``workloads``."""
    twin = next(e for e in bench.manifest["per_layer"] if like.items() <= bench.spec(e, "args").items())
    spec = copy.deepcopy(bench.specs[twin["name"]])
    spec["args"].update(args)
    bench.manifest["per_layer"] = [e for e in bench.manifest["per_layer"] if e["name"] != name] + [
        {**twin, "name": name, "workloads": workloads}]
    bench.specs[name] = spec


def drop(bench, cell, reader):
    bench.manifest["per_layer"] = [e for e in bench.manifest["per_layer"]
                                   if e not in bench.listing(cell) or bench.spec(e, "reader") != reader]


KIMI, LFM2, LING3, GRANITE, OLMO, MINICPM = (f"{c}_epix_saturated" for c in (
    "kimi_k2", "lfm2", "ling3", "granite", "olmo_hybrid", "minicpm_sala"))
BROKEN = {  # what a PR did -> (how, the rule that refuses it, where, what a refusal must name)
    "a_metric_file_has_no_entry": (
        lambda b: b.specs.update({"orphan_ms.hit": {}}), files_without_an_entry, (), ["orphan_ms.hit"]),
    "conv_ms.lfm2_lists_kimi_s_cell": (
        lambda b: put(b, "conv_ms.lfm2", {"scope": "conv"}, [KIMI]),
        suffix_and_moves, (KIMI,), ["conv_ms.lfm2", KIMI]),
    "workloads_are_out_of_order": (
        lambda b: put(b, "order_ms.hit", {"scope": "proj"}, list(reversed(DECODER_CELLS))),
        order_not_place, (LFM2,), ["order_ms.hit", LFM2]),
    "a_hit_entry_within_gdn_kernel_lists_granite": (
        lambda b: put(b, "gdn_ms.hit", {"scope": "conv"}, [GRANITE, OLMO], within="@gdn_kernel"),
        fits_the_cell, (GRANITE,), ["gdn_ms.hit", GRANITE, "@gdn_kernel"]),
    "a_shape_from_value_is_no_configuration_key": (
        lambda b: put(b, "step_mfu.ling3", {"function": "ling3.step"}, [LING3], shape_from={"batch": "batch"}),
        fits_the_cell, (LING3,), ["step_mfu.ling3", LING3, "'batch'"]),
    "a_host_path_entry_forgets_minicpm_sala": (
        lambda b: put(b, "stage_ms.hit", {"histogram": "stages.launch"},
                      [c for c in b.hit_cells if c != MINICPM]),
        host_path_is_every_hit_cell_s, (MINICPM,), ["stage_ms.hit", MINICPM]),
    "kda_ms.laguna_has_kda_ms.ling3_s_file": (
        lambda b: put(b, "kda_ms.laguna", {"scope": "kda"}, ["laguna_epix_saturated"]),
        one_reading_one_name, ("kda_ms",), ["kda_ms.laguna", "ONE reading"]),
    "proj_ms.hit_stands_beside_the_cells_own": (
        lambda b: [put(b, name, {"scope": "proj"}, [cell])
                   for name, cell in (("proj_ms.hit", GRANITE), ("proj_ms.kimi", KIMI))],
        one_reading_one_name, ("proj_ms",), ["proj_ms.hit", "proj_ms.kimi", "half folded"]),
    "step_mfu.ling3_is_taken_away": (
        lambda b: drop(b, LING3, "peak_flops_share"),
        a_share_of_the_peak, (LING3,), [LING3, "peak_flops_share"]),
    "a_kda_ms_entry_lists_lfm2": (
        lambda b: put(b, "kda_ms.ling3", {"scope": "kda"}, [LFM2, LING3]),
        scope_where_a_layer_opens_it, (LFM2,), ["kda_ms.ling3", LFM2, "'kda'"]),
}


@pytest.mark.parametrize("broken", sorted(BROKEN))
def test_a_rule_refuses_what_a_careless_pr_did_and_names_it(broken):
    how, rule, where, names = BROKEN[broken]
    bench = copy.deepcopy(BENCH)
    assert not rule(bench, *where)  # it stood
    how(bench)
    refused = rule(bench, *where)
    assert any(all(n in line for n in names) for line in refused), refused
