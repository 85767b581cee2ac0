"""Device scopes: every operation of the step and chunk programs is named by
the code that asked for it (``jax.named_scope`` names of
``lumen_tpu/utils/trace.py``, carried as ``op_name`` metadata into the
compiled program and a profile), and the benchmark's readers of those names.

(a) The optimized HLO of the step, chunk, finish and admit programs of a tiny
grouped-query, latent and hybrid decoder holds each name where it is placed.
(b) ``benchmark/trace_scopes.py`` joins a real profile's operations to the
names, and the reduction and every accepted reader read the same numbers
from a trace with the scopes beside it as without; the three scope readers
read nothing without them."""

from __future__ import annotations

import contextlib
import copy
import functools
import glob
import json
import os
import re

import pytest

import jax
import jax.numpy as jnp

import test_greedy_sampler as tiny
import test_vlm_hybrid as hybrid
from benchmark import cells, trace_reduce, trace_scopes
from lumen_tpu.models.vlm.continuous import ContinuousScheduler
from lumen_tpu.models.vlm.generate import Generator
from lumen_tpu.utils import trace

PROGRAMS = ("_step_block", "_prefill_chunk", "_chunk_finish", "_admit")
ARCHS = ("qwen2", "dots3", "granite")
STEP_SCOPES = {trace.SCOPE_STATE, trace.SCOPE_EMBED, trace.SCOPE_DECODE, trace.SCOPE_SAMPLE}
DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests", "data")


def _shape_of(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype) if isinstance(x, jax.Array) else x


@functools.lru_cache(maxsize=None)
def compiled_programs(arch: str) -> dict[str, str]:
    """The optimized HLO text of each program one request runs through the
    scheduler (three lane chunks, an admission, a finish, decode blocks),
    compiled from the shapes of its first call."""
    vcfg, model, params = tiny.decoder(arch)
    gen = Generator(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
    calls = {}
    for name in PROGRAMS:
        fn = getattr(gen, name)

        def tap(*args, _fn=fn, _name=name, **kwargs):
            calls.setdefault(_name, (_fn, jax.tree.map(_shape_of, (args, kwargs))))
            return _fn(*args, **kwargs)

        setattr(gen, name, tap)
    sched = ContinuousScheduler(gen, params, slots=tiny.SLOTS, block=tiny.BLOCK, name=f"scopes-{arch}",
                                page_size=tiny.PAGE, prefill_chunk=tiny.CHUNK)
    try:
        ids = list(range(3, 3 + 2 * tiny.CHUNK + 5))
        sched.submit(hybrid.request(model, params, ids, 4)).result(timeout=300)
    finally:
        sched.close()
    assert sorted(calls) == sorted(PROGRAMS)
    with no_persistent_cache():  # an executable loaded from it prints no metadata
        return {name: fn.lower(*args, **kwargs).compile().as_text() for name, (fn, (args, kwargs)) in calls.items()}


@contextlib.contextmanager
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()  # nor one the scheduler's own call left in memory
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def instructions(arch: str, program: str) -> list[tuple[str, str, str]]:
    """``(shape, opcode, op_name)`` of every instruction of the program that
    carries an ``op_name``, fused computations' bodies included."""
    out = []
    for line in compiled_programs(arch)[program].splitlines():
        m, n = trace_scopes._INSTR.match(line), trace_scopes._OP_NAME.search(line)
        if m and n:
            out.append((re.sub(r"\{[^}]*\}", "", m.group(2)), m.group(3), n.group(1).split(";")[0]))
    return out


def lumen_names(op_name: str) -> list[str]:
    return [p for p in op_name.split("/") if p.startswith("lumen.")]


def find(arch, program, opcode, pattern, shape=None) -> list[tuple[str, str, str]]:
    rx = re.compile(pattern)
    return [i for i in instructions(arch, program)
            if i[1] == opcode and rx.search(i[2]) and (shape is None or i[0] == shape)]


def vocab(arch) -> int:
    return tiny.decoder(arch)[0].decoder.vocab_size


# -- (a) where each name is placed ------------------------------------------------


@pytest.mark.parametrize("arch", ARCHS)
def test_the_step_writes_its_pages_under_kv_write_inside_decode(arch):
    writes = find(arch, "_step_block", "scatter", r"lumen\.decode/.*lumen\.kv_write/scatter$")
    assert writes, "no page write under lumen.decode/.../lumen.kv_write"
    assert not find(arch, "_step_block", "scatter", r"^(?!.*lumen\.kv_write).*lumen\.decode/.*/scatter$")


def test_a_recurrent_layers_state_update_is_its_write():
    """Granite's Mamba layers rewrite the slot's tail and state in the
    one-token update: both are under ``lumen.kv_write``."""
    ops = [i for i in instructions("granite", "_step_block") if "lumen.kv_write" in i[2]]
    state_shapes = {s for s, _, n in ops if "mixer" in n.lower() or "mamba" in n.lower()}
    assert state_shapes, sorted({n for _, _, n in ops})[:10]


@pytest.mark.parametrize("arch", ARCHS)
def test_the_seen_scatter_is_the_steps_state(arch):
    seen = find(arch, "_step_block", "scatter", r"lumen\.state/scatter-max$", f"pred[{tiny.SLOTS},{vocab(arch)}]")
    assert seen and all(lumen_names(n) == [trace.SCOPE_STATE] for _, _, n in seen)


@pytest.mark.parametrize("arch", ARCHS)
def test_the_penaltys_select_and_the_argmax_are_the_steps_sample(arch):
    v = vocab(arch)
    assert find(arch, "_step_block", "select", r"lumen\.sample/(jit\(_where\)/)?select_n$", f"f32[{tiny.SLOTS},{v}]")
    argmax = [i for i in instructions(arch, "_step_block") if i[1] == "reduce" and i[0] == f"(f32[{tiny.SLOTS}], s32[{tiny.SLOTS}])"]
    assert argmax and all(lumen_names(n) == [trace.SCOPE_SAMPLE] for _, _, n in argmax)


@pytest.mark.parametrize("arch", ARCHS)
def test_every_scope_of_the_step_is_one_of_its_four_and_kv_write_lies_in_decode(arch):
    seen = set()
    for _, _, name in instructions(arch, "_step_block"):
        names = lumen_names(name)
        if not names:
            continue
        assert names[0] in STEP_SCOPES, name
        assert names[1:] in ([], [trace.SCOPE_KV_WRITE]), name
        assert names[1:] == [] or names[0] == trace.SCOPE_DECODE, name
        seen.add(names[0])
    assert seen == STEP_SCOPES


@pytest.mark.parametrize("arch", ARCHS)
def test_a_chunk_is_prefill_and_writes_its_scratch_under_kv_write(arch):
    named = [lumen_names(n) for _, _, n in instructions(arch, "_prefill_chunk") if lumen_names(n)]
    assert named and all(names[0] == trace.SCOPE_PREFILL for names in named)
    assert find(arch, "_prefill_chunk", "dynamic-update-slice", r"lumen\.prefill/.*lumen\.kv_write/dynamic_update_slice$")


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("program, scope", [("_chunk_finish", trace.SCOPE_SAMPLE), ("_admit", trace.SCOPE_INSTALL)])
def test_the_finish_is_sample_and_the_admission_install(arch, program, scope):
    named = [lumen_names(n) for _, _, n in instructions(arch, program) if lumen_names(n)]
    assert named and all(names == [scope] for names in named)
    if program == "_admit":
        assert find(arch, program, "scatter", r"lumen\.install/scatter$")
    else:
        assert [i for i in instructions(arch, program) if i[1] == "reduce" and i[0] == "(f32[1], s32[1])"]


# -- (b) a profile joined to the names, and the readers ------------------------------


def test_the_scope_path_keeps_the_lumen_part_without_wrappers_and_indices():
    name = ("jit(_step_block_impl)/while/body/closed_call/lumen.decode/VLMModel.decode_paged/decoder/"
            "layers_12/attn/lumen.kv_write/jit(floor_divide)/div")
    assert trace_scopes.scope_path(name) == "lumen.decode/VLMModel.decode_paged/decoder/layers/attn/lumen.kv_write"
    assert trace_scopes.scope_path("jit(_step_block_impl)/while/body/lumen.sample/cond/branch_1_fun/sort") == \
        "lumen.sample"
    assert trace_scopes.scope_path("jit(_step_block_impl)/while/body/add") == ""
    assert trace_scopes.scope_path("") == ""


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A real profile (this backend's) of a program with two scopes and an
    operation under neither."""

    def f(x, y):
        with jax.named_scope(trace.SCOPE_STATE):
            y = y.at[jnp.arange(4), 3].set(1.0)
        with jax.named_scope(trace.SCOPE_DECODE):
            z = jnp.tanh(x @ x.T) + y.sum()
        return z, y

    jf = jax.jit(f)
    x, y = jnp.ones((128, 128)), jnp.zeros((4, 64))
    jax.block_until_ready(jf(x, y))
    d = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(d)
    for _ in range(3):
        jax.block_until_ready(jf(x, y))
    jax.profiler.stop_trace()
    return trace_reduce.find_xplane(d)


def test_a_profile_keeps_each_programs_hlo_and_each_operation_joins_its_scope(profiled):
    """This backend lays its operations on a host line with their program's
    name, id and run as stats; laid out as a device's lines (one ``XLA
    Modules`` event a run), they join their instructions by time."""
    from jax.profiler import ProfileData

    protos = trace_scopes.hlo_protos(profiled)
    (program,) = [k for k in protos if k.startswith("jit_f(")]
    table = trace_scopes.instructions(protos[program])
    assert any(trace.SCOPE_DECODE in n for n, _ in table.values())
    ops, runs = [], {}
    for plane in ProfileData.from_file(profiled).planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if f"{stats.get('hlo_module')}({stats.get('program_id')})" == program:
                    ops.append([f"%{stats['hlo_op']}", int(ev.start_ns), int(ev.duration_ns)])
                    lo, hi = runs.get(stats["run_id"], (ev.start_ns, ev.start_ns))
                    runs[stats["run_id"]] = (min(lo, ev.start_ns), max(hi, ev.start_ns + ev.duration_ns))
    assert len(runs) == 3
    neutral = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [[program, lo, hi - lo] for lo, hi in runs.values()]},
        {"name": "XLA Ops", "events": ops}]}]}
    (mine,) = trace_scopes.scoped_form(neutral, protos).values()
    assert len(mine) == len(ops) and all(len(op) == 6 and op[3] == "jit_f" for op in mine)
    assert {trace.SCOPE_STATE, trace.SCOPE_DECODE} <= {op[4] for op in mine}
    dots = [op for op in mine if trace_reduce.stem(op[0]).startswith("dot")]
    assert dots and all(op[4] == trace.SCOPE_DECODE and op[5].startswith("f32[128,128]") for op in dots)
    ranked = trace_scopes.device_scopes({"/device:TPU:0": mine})
    assert ranked[0][0].startswith(trace.SCOPE_DECODE + "/dot")
    assert all(s > 0 for _, s in ranked)
    # a run named by another id than the protos': the program of that name stands in
    other = json.loads(json.dumps(neutral).replace(program, "jit_f(999999)"))
    (again,) = trace_scopes.scoped_form(other, protos).values()
    assert [op[4:] for op in again] == [op[4:] for op in mine]


def hand_trace() -> dict:
    """Two runs of a decode block of 8 steps and a prefill chunk on one
    device, the kernels the accepted readers match, the host's phases."""
    step, chunk = "jit__step_block_impl(11)", "jit__prefill_chunk_impl(12)"
    ops = [
        # run 1 of the step program [0, 1000)
        ["%while.3", 0, 1000], ["%copy.7", 0, 200], ["%fusion.1", 200, 100],
        ["%paged_attention_varq_kernel.2", 300, 150], ["%scatter.4", 450, 50], ["%copy-start.1", 500, 10],
        ["%copy-done.1", 510, 90], ["%reduce.9", 600, 40], ["%select.2", 640, 60], ["%ragged-dot-none.1", 700, 100],
        ["%ssm_state_update_kernel.1", 800, 50], ["%latent_paged_attention_kernel.1", 850, 50],
        ["%indexer_scores_kernel.1", 900, 50],
        # the chunk [1200, 1700)
        ["%ssd_chunk_scan.1", 1200, 300], ["%fusion.2", 1500, 200],
        # run 2 of the step program [2000, 3000)
        ["%while.3", 2000, 1000], ["%copy.7", 2000, 200], ["%fusion.1", 2200, 100],
        ["%paged_attention_varq_kernel.2", 2300, 150], ["%scatter.4", 2450, 50], ["%reduce.9", 2600, 40],
        ["%select.2", 2640, 60],
    ]
    scopes = {
        "while.3": "", "copy.7": "", "copy-start.1": "", "copy-done.1": "",
        "fusion.1": "lumen.decode/VLMModel.decode_paged/decoder/layers/attn",
        "paged_attention_varq_kernel.2": "lumen.decode/VLMModel.decode_paged/decoder/layers/attn",
        "scatter.4": "lumen.decode/VLMModel.decode_paged/decoder/layers/attn/lumen.kv_write",
        "reduce.9": "lumen.sample", "select.2": "lumen.sample", "ragged-dot-none.1": "lumen.decode/moe",
        "ssm_state_update_kernel.1": "lumen.decode/mixer/lumen.kv_write",
        "latent_paged_attention_kernel.1": "lumen.decode/attn", "indexer_scores_kernel.1": "lumen.decode/attn",
        "ssd_chunk_scan.1": "lumen.prefill/mixer", "fusion.2": "lumen.prefill/mlp",
    }
    return {
        "planes": [
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": [[step, 0, 1000], [chunk, 1200, 500], [step, 2000, 1000]]},
                {"name": "XLA Ops", "events": ops},
            ]},
            {"name": "/host:CPU", "lines": [{"name": "vlm-continuous/11", "events": [
                ["lumen:vlm.block.dispatch", 1000, 150], ["lumen:vlm.prefill_chunk", 1100, 90],
                ["lumen:vlm.block.dispatch", 1750, 200], ["XlaLinearize", 1960, 30]]}]},
        ],
        "_scopes": scopes,
    }


def with_scopes(tr: dict, parent: bool = False) -> dict:
    """``tr`` with the scoped form beside its planes: the one recorded with
    it, else one made from its hand-given scopes (a parent's program: every
    operation without a scope)."""
    if "scopes" in tr and not parent:
        return tr
    out = copy.deepcopy(bare(tr))
    scopes = tr.get("_scopes", {})
    scoped = {}
    for plane in trace_reduce.device_planes(out):
        runs = [ev for line in plane["lines"] if line["name"] == "XLA Modules" for ev in line["events"]]
        rows = []
        for name, start, dur in trace_reduce.op_events(plane):
            run = next((r[0] for r in runs if r[1] <= start < r[1] + r[2]), "")
            op = name.lstrip("%")
            rows.append([name, start, dur, run.split("(")[0], "" if parent else scopes.get(op, ""), "f32[8]"])
        scoped[plane["name"]] = rows
    out["scopes"] = scoped
    return out


def bare(tr: dict) -> dict:
    return {k: v for k, v in tr.items() if k not in ("_scopes", "scopes")}


def recorded() -> list[tuple[str, dict]]:
    out = [("hand", hand_trace())]
    for path in sorted(glob.glob(os.path.join(DATA, "*.excerpt.json"))):
        with open(path) as f:
            out.append((os.path.basename(path), json.load(f)))
    return out


TRACES = [name for name, _ in recorded()]
CAPTION = "hub-vitl14-qwen2-1p5b.caption_storm"
NEW = ("step_unscoped_pct", "step_kv_write_ms", "step_sample_ms")


def window_result() -> dict:
    """The window's client view and counters, as ``run.py`` hands them over."""
    vlm = {"pending_ms_sum": 120.0, "pending_count": 4, "prefill_chunks_run": 8, "lane_turns": 4,
           "latent_keys_sum": 41000, "rows_stepped": 10, "lane_ms_sum": 300.0, "lane_jobs": 2,
           "first_token_ms_sum": 900.0, "first_token_count": 3, "window_pages_freed": 50, "admitted": 3,
           "preempted": 1, "blocks_run": 5, "moe_tokens_held": 400, "moe_layer_calls": 20}
    after = {"gauges": {"vlm-continuous:BenchVLM": vlm,
                        "batcher:clip-image": {"collect_wait_ms_sum": 50.0, "collect_items": 10},
                        "batch-occupancy:clip-image": {"items": 40, "batches": 8},
                        "bulk-lane": {"queue_ms_sum": 70.0, "items": 7},
                        "decode_pool": {"wait_ms_sum": 30.0, "wait_count": 6, "wait_ms_p50": 4.0}},
             "counters": {"xla_compiles": 3}}
    before = json.loads(json.dumps(after).replace("120.0", "20.0"))
    client = {"attempted": 10, "completed_in_window": 9, "tokens_in_window": 900, "tokens_total": 1000,
              "window_s": 40.0, "failed": 0, "latency_ms": [10.0, 12.0, 30.0], "itl_ms": [20.0, 25.0, 90.0],
              "ttft_ms": [400.0, 500.0, 800.0]}
    return {"client": client, "before": before, "after": after, "seconds": 40.0,
            "settings": {"vlm": {"decode_block": 8}}}


def context(cell_name: str, tr: dict) -> dict:
    from benchmark.generators.common import median, percentile

    return {"cell": cells.Cell(cell_name), "result": window_result(), "trace": tr, "reduced": trace_reduce.reduce(tr),
            "peaks": cells.peaks("TPU v5 lite"), "median": median, "percentile": percentile}


def outcome(metric: dict, ctx: dict):
    cell = ctx["cell"]
    spec, reader = cell.layer_metric(metric["name"])
    try:
        return reader.read(ctx, spec)
    except Exception as e:  # noqa: BLE001 - both sides must fail alike
        return type(e).__name__


def accepted_metrics() -> list[dict]:
    bench = cells._read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    return [m for m in bench["per_layer"] if m["name"] not in NEW]


@pytest.mark.parametrize("trace_name", TRACES)
def test_the_reduction_reads_the_same_with_the_scopes_beside(trace_name):
    tr = dict(recorded())[trace_name]
    plain, scoped = bare(tr), with_scopes(tr)
    assert trace_reduce.reduce(scoped) == trace_reduce.reduce(plain)
    for plane in trace_reduce.device_planes(plain):
        assert all(len(ev) == 3 for ev in trace_reduce.op_events(plane))
    assert trace_reduce.time_by_name(scoped) == trace_reduce.time_by_name(plain)
    assert trace_reduce.seconds_matching(scoped, "paged") == trace_reduce.seconds_matching(plain, "paged")
    assert trace_reduce.busy_seconds(scoped) == trace_reduce.busy_seconds(plain)
    assert trace_reduce.idle_gaps(scoped) == trace_reduce.idle_gaps(plain)


@pytest.mark.parametrize("trace_name", TRACES)
@pytest.mark.parametrize("metric", [m["name"] for m in accepted_metrics()])
def test_every_accepted_reader_reads_the_same_with_the_scopes_beside(metric, trace_name):
    (m,) = [x for x in accepted_metrics() if x["name"] == metric]
    tr = dict(recorded())[trace_name]
    cell = (m.get("workloads") or [CAPTION])[0]
    assert outcome(m, context(cell, with_scopes(tr))) == outcome(m, context(cell, bare(tr)))


def test_the_accepted_device_readers_read_numbers_from_the_hand_trace():
    """The comparison above is of numbers, not of Nones."""
    tr = hand_trace()
    read = {m["name"]: outcome(m, context((m.get("workloads") or [CAPTION])[0], with_scopes(tr)))
            for m in accepted_metrics() if m["source"] == "device_trace"}
    numbers = [n for n, v in read.items() if isinstance(v, float)]
    for name in ("vlm_step_hbm_pct", "paged_attn_time_pct", "prefill_time_pct.caption", "device_idle_pct.caption",
                 "sched_host_ms_per_block", "latent_attn_time_pct.full", "ssm_update_time_pct"):
        assert name in numbers, read


@pytest.mark.parametrize("metric", NEW)
@pytest.mark.parametrize("trace_name", TRACES)
def test_a_scope_reader_reads_nothing_without_scopes(metric, trace_name):
    (m,) = [x for x in cells._read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))["per_layer"] if x["name"] == metric]
    tr = dict(recorded())[trace_name]
    for cell in m["workloads"]:
        assert outcome(m, context(cell, bare(tr))) is None
        assert outcome(m, context(cell, with_scopes(tr, parent=True))) is None


def test_the_scope_readers_on_the_hand_trace():
    """Two runs of an 8-step block: 16 steps. The step program's operations,
    loops left out: copy 400 + copy-start 10 + copy-done 90 unscoped of
    1,550 in all; kv_write 100 + 50; sample 200."""
    (m_unscoped, m_kv, m_sample) = [
        x for name in NEW for x in cells._read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))["per_layer"]
        if x["name"] == name]
    ctx = context(CAPTION, with_scopes(hand_trace()))
    assert outcome(m_unscoped, ctx) == pytest.approx(100.0 * 500 / 1550)
    assert outcome(m_kv, ctx) == pytest.approx(150 / 1e6 / 16)
    assert outcome(m_sample, ctx) == pytest.approx(200 / 1e6 / 16)


def test_the_scoped_form_is_read_once_a_run_and_kept():
    ctx = context(CAPTION, bare(hand_trace()))
    assert trace_scopes.of(ctx) == {} and ctx["scopes"] == {}
    ctx["scopes"] = {"sentinel": []}
    assert trace_scopes.of(ctx) == {"sentinel": []}


def test_the_vocabulary_is_one_prefix_and_each_name_once():
    names = [v for k, v in vars(trace).items() if k.startswith("SCOPE_")]
    assert len(names) == len(set(names)) == 7
    assert all(re.fullmatch(r"lumen\.[a-z_]+", n) for n in names)


@pytest.mark.parametrize("metric", NEW)
def test_each_scope_metric_has_its_file_its_reader_and_the_four_caption_cells(metric):
    bench = cells._read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    assert entry["source"] == "device_trace" and entry["layer"] == "model step"
    assert entry["moves"] == "caption_tokens_per_s"
    (rate,) = [m for m in bench["end_to_end"] if m["name"] == entry["moves"]]
    assert entry["workloads"] == rate["workloads"]
    for name in entry["workloads"]:
        cell = cells.Cell(name)
        spec, module = cell.layer_metric(metric)
        assert spec["reader"] == "scope_time" and callable(module.read)
        assert spec["module_pattern"] == "step_block" and spec["per"] in ("step", "share")


@pytest.mark.parametrize("metric", NEW)
def test_the_scope_readers_on_a_recorded_block_and_on_its_parent(metric):
    """A decode block recorded on the chip with the scopes (its step program
    built with them) reads a number; the parent's block, whose step program
    carries none, reads nothing, though another program of its process may
    carry the names (the compile cache ignores op metadata)."""
    (m,) = [x for x in cells._read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))["per_layer"] if x["name"] == metric]
    traces = dict(recorded())
    value = outcome(m, context(CAPTION, traces["caption_storm.scoped.excerpt.json"]))
    assert isinstance(value, float) and value > 0
    assert value < 100 if m["unit"] == "%" else True
    assert outcome(m, context(CAPTION, traces["caption_storm.unscoped.excerpt.json"])) is None
