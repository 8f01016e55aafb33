"""The program's tracing layer (``repro.obs``): spans in a profiler trace,
compile counters, and device scopes in the OMP round's HLO metadata."""

from __future__ import annotations

import glob
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import gradmatch, omp, selection

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import program_trace  # noqa: E402

ROUND_SCOPES = ("omp.score", "omp.column", "omp.nnls")


def _pool(n=120, d=12, classes=3):
    g = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.float32)
    return g, jnp.arange(n) % classes


def _trace_file(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    return paths[0]


def test_a_select_span_lands_in_a_cpu_trace(tmp_path):
    g, labels = _pool()

    def call():
        return selection.select("gradmatch", jax.random.PRNGKey(1), g, 12,
                                labels=labels, num_classes=3)

    jax.block_until_ready(call())                  # compile outside
    calls_before = obs.counters().get("select.calls", 0)
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(call())
    path = _trace_file(str(tmp_path))

    from jax.profiler import ProfileData
    stats = [dict(e.stats) for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events
             if e.name == "repro.select"]
    assert stats == [{"strategy": "gradmatch", "call": calls_before + 1}]

    _, host = program_trace.read_trace(path, {})
    names = [n for _, _, n in host]
    for span in ("repro.select", "repro.gradmatch.budget",
                 "repro.gradmatch.targets", "repro.omp.per_class",
                 "repro.count.select.calls"):
        assert span in names, span
    # Every span of the call nests inside the call's own span.
    (s0, d0, _), = [h for h in host if h[2] == "repro.select"]
    for s, d, n in host:
        if n.startswith("repro.") and n != "repro.count.select.calls":
            assert s0 <= s and s + d <= s0 + d0, n
    # The reweight and the objective run inside the call's one program:
    # device scopes, not host spans.
    for span in ("repro.omp.reweight", "repro.gradmatch.err"):
        assert span not in names, span
    table, valid = omp._class_table(labels, 3)
    targets = jax.nn.one_hot(labels, 3).T @ g
    locs = _locations(gradmatch._per_class.lower(
        g, targets, table, valid, np.full(3, 4, np.int32), 0.5, 1e-10, k=4,
        method="incremental"))
    for scope, op in (("omp.reweight", "while"), ("gradmatch.err", "sub")):
        scoped = [loc for loc in locs if scope + "/" in loc]
        assert any(op in loc.split(scope + "/", 1)[1].split("/")
                   for loc in scoped), scope
        assert {program_trace.scope_of(loc) for loc in scoped} == {scope}


def test_spans_and_markers_record_nothing_without_a_profiler():
    before = obs.counters().get("test.obs.count", 0)
    with obs.span("test.obs", call=1):
        total = obs.count("test.obs.count", 2)
    assert total == before + 2
    assert obs.counters()["test.obs.count"] == before + 2


def test_class_counters_read_the_rows_a_call_scores():
    # Classes of 5, 20, 0 and 15 rows and 10 rows in no class: the class
    # slab is 4 x 20 rows, 40 of them padding.
    sizes = [5, 20, 0, 15]
    labels = jnp.asarray(np.random.default_rng(0).permutation(
        np.concatenate([np.repeat(np.arange(4), sizes), [-1] * 5, [4] * 5])))
    g = jax.random.normal(jax.random.PRNGKey(2), (labels.shape[0], 12))
    before = obs.counters()
    jax.block_until_ready(gradmatch.gradmatch_per_class(g, labels, 4, 12))
    after = obs.counters()
    rows = after["omp.class_rows"] - before.get("omp.class_rows", 0)
    pad = after["omp.class_pad_rows"] - before.get("omp.class_pad_rows", 0)
    assert (rows, pad) == (4 * 20, 4 * 20 - 40)


def test_a_repeated_per_class_call_traces_and_loads_nothing():
    """The per-class path is one compiled program keyed on the shapes; the
    quotas are traced, so other class sizes at the same slab width and
    round budget reuse it."""
    g, labels = _pool()                      # classes of 40, 40, 40
    # Classes of 40, 30 and 40 and 10 rows in none: the slab is still 40
    # wide, and k 11 splits 4, 3, 4 against the first pool's 4, 4, 4.
    relabelled = jnp.asarray(np.random.default_rng(1).permutation(
        np.repeat([0, 1, 2, -1], [40, 30, 40, 10])))
    jax.block_until_ready(gradmatch.gradmatch_per_class(g, labels, 3, 12))
    for lab, k in ((labels, 12), (relabelled, 11)):
        before = obs.counters()
        res = jax.block_until_ready(
            gradmatch.gradmatch_per_class(g, lab, 3, k))
        after = obs.counters()
        assert int(res.mask.sum()) == k
        for key in ("jax.traces", "jax.program_loads"):
            assert after.get(key, 0) - before.get(key, 0) == 0, (key, k)


def test_a_new_program_is_loaded_once_and_traced():
    x = jnp.arange(7.0)

    @jax.jit
    def f(v):
        return jnp.sin(v) * 3.0 + 0.25

    before = obs.counters()
    jax.block_until_ready(f(x))
    after = obs.counters()
    jax.block_until_ready(f(x))
    again = obs.counters()

    def delta(a, b, key):
        return b.get(key, 0) - a.get(key, 0)

    assert delta(before, after, "jax.program_loads") == 1
    assert delta(before, after, "jax.traces") >= 1
    assert delta(before, after, "jax.compile_s") > 0
    for key in ("jax.program_loads", "jax.traces", "jax.compile_s",
                "jax.cache_reads"):
        assert delta(after, again, key) == 0, key


def _locations(lowered) -> set:
    return set(re.findall(r'loc\("([^"]*)"',
                          lowered.as_text(debug_info=True)))


# One characteristic operation per scope: the taken-mask scatter of the
# scoring, the cache or index writes of the column, the NNLS loop.
WRAPPED = {"omp.score": "scatter", "omp.column": "scatter",
           "omp.nnls": "while"}


@pytest.mark.parametrize("method", ["incremental", "dense"])
def test_omp_round_scopes_reach_the_op_name_metadata(method):
    g, _ = _pool()
    locs = _locations(omp.omp_select.lower(g, jnp.sum(g, 0), k=6,
                                           method=method))
    for scope in ROUND_SCOPES:
        ops = {loc.split(scope + "/", 1)[1].split("/")[0]
               for loc in locs if scope + "/" in loc}
        assert WRAPPED[scope] in ops, (scope, sorted(ops))
        assert program_trace.scope_of(
            f"jit(omp_select)/while/body/{scope}/{WRAPPED[scope]}") == scope
    if method == "incremental":
        assert any("omp.init/" in loc for loc in locs)
        assert any("omp.prefix/" in loc for loc in locs)


def test_compiled_programs_keep_the_scopes_by_instruction():
    """The benchmark finds a trace op's scope through the loaded
    program's optimized HLO, by program and instruction name."""
    g, _ = _pool()
    jax.block_until_ready(omp.omp_select(g, jnp.sum(g, 0), k=6))
    names = program_trace.hlo_op_names(
        jax.devices()[0].client.live_executables())
    scopes = {program_trace.scope_of(op)
              for (program, _), op in names.items()
              if program == "jit_omp_select"}
    assert set(ROUND_SCOPES) <= scopes


def test_batched_and_session_rounds_carry_the_same_scopes():
    g, _ = _pool()
    targets = jnp.stack([jnp.sum(g, 0), jnp.sum(g[:40], 0)])
    locs = _locations(omp.omp_select_batched.lower(g, targets, k=6))
    sess = omp.omp_session_start(g, jnp.sum(g, 0), 0)
    locs_s = _locations(omp._run_session_block.lower(
        g, sess.target, sess.c0, sess.valid,
        omp._grow_prefix(sess.st, sess.block, keep_cols=True), 0, 4,
        use_cols=True, lam=sess.lam, eps=sess.eps,
        nnls_iters=sess.nnls_iters, absolute=False))
    for scope in ROUND_SCOPES:
        assert any(scope + "/" in loc for loc in locs), scope
        assert any(scope + "/" in loc for loc in locs_s), scope
