"""The stage reduction (``bench/stages.py``) on a small synthetic trace
with scoped ops, its reading of compiled HLO, how it tells the program
that ran an op, the per-stage readers (``bench/metrics/<stage>_ms.train
.py`` and ``unstaged_ms.train.py``), and the serving reader
``batch_occupancy.serve.py`` on a tiny cell on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench import stages as S
from bench import trace as T
from bench.tests import tiny
from bench.tests.test_bench_trace import _trace

SCOPES = {"fusion.1": "jit(iter_fn)/while/body/ivec_preselect/add",
          "fusion.2": "jit(iter_fn)/ivec_mstep/ivec_sigma_floor/mul",
          "_matmul_kernel": "jit(iter_fn)/ivec_estep/jit(tvm_estep_a)",
          "custom-call.7:EighTpu": "jit(iter_fn)/ivec_mstep/eigh",
          "copy.3": "jit(iter_fn)/while"}


def _scoped():
    tr = _trace()
    tr.devices[0].append(T.Event("custom-call.7:EighTpu", 7.5, 8.0))
    return S.with_scopes(tr, SCOPES)


HLO = """HloModule jit_f, is_scheduled=true
%fused_scatter (p0: f32[8,4], p1: s32[8], p2: f32[8,4]) -> f32[8,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p2 = f32[8,4]{1,0} parameter(2)
  %mul.2 = f32[8,4]{1,0} multiply(%p2, %p2), metadata={op_name="jit(f)/ivec_moments/mul"}
  ROOT %scatter.1 = f32[8,4]{1,0} scatter(%p0, %p1, %mul.2), update_window_dims={1}
}

%fused_outer (q0: f32[8,4]) -> f32[8,4] {
  %q0 = f32[8,4]{1,0} parameter(0)
  ROOT %fusion.5 = f32[8,4]{1,0} fusion(%q0), kind=kCustom, calls=%fused_scatter
}

ENTRY %main.4 (x.1: f32[8,4]) -> f32[8,20] {
  %fusion.1 = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/ivec_rescore/exp"}
  %fusion.6 = f32[8,4]{1,0} fusion(%x.1), kind=kCustom, calls=%fused_outer
  %copy.3 = f32[4]{0} copy(%fusion.1)
  %sort.8 = f32[4]{0} sort(%fusion.1), to_apply=%cmp, metadata={op_name="sort"}
  %ragged-dot-none = f32[4]{0} custom-call(%sort.8, %fusion.6), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %custom-call.7 = (f32[8,20]) custom-call(%x.1), custom_call_target="TopK", metadata={op_name="jit(f)/ivec_preselect/top_k" stack_frame_id=3}
}

%cmp (a: f32[], b: f32[]) -> pred[] {
  %a = f32[] parameter(0), metadata={op_name="sort"}
  %b = f32[] parameter(1)
  ROOT %lt.1 = pred[] compare(%a, %b), direction=LT
}
"""


def test_scopes_are_read_from_compiled_hlo_text():
    # a fusion the compiler left without an op_name takes its called
    # computation's: its root's, else the one its instructions carry; an
    # op the compiler named after itself takes its operands'; only ops
    # that can run on the device are keyed, as a trace names them
    assert S.hlo_scopes(HLO) == {
        "fusion.1": "jit(f)/ivec_rescore/exp",
        "fusion.6": "jit(f)/ivec_moments/mul",
        "copy.3": "jit(f)/ivec_rescore/exp",
        "sort.8": "jit(f)/ivec_rescore/exp",
        "ragged-dot-none": "jit(f)/ivec_rescore/exp",
        "custom-call.7:TopK": "jit(f)/ivec_preselect/top_k"}


def test_a_compiler_named_op_takes_the_scope_most_of_its_operands_carry():
    hlo = HLO.replace("custom-call(%sort.8, %fusion.6)",
                      "custom-call(%fusion.6, %sort.8, %fusion.6)")
    assert S.hlo_scopes(hlo)["ragged-dot-none"] == \
        "jit(f)/ivec_moments/mul"


# a sort the compiler adds, with no op_name, on an iota it shares with
# another stage, feeding the moments
SHARED = """HloModule jit_f, is_scheduled=true
ENTRY %main.5 (x.1: s32[8]) -> f32[8] {
  %iota.1 = s32[8]{0} iota(), iota_dimension=0, metadata={op_name="jit(f)/ivec_estep/iota"}
  %sort.2 = (s32[8]{0}, s32[8]{0}) sort(%x.1, %iota.1), dimensions={0}, to_apply=%cmp
  %get-tuple-element.3 = s32[8]{0} get-tuple-element(%sort.2), index=1
  ROOT %fusion.4 = f32[8]{0} fusion(%get-tuple-element.3), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/ivec_moments/gather"}
}
"""


def test_a_compiler_made_op_takes_the_scope_of_the_work_it_feeds():
    scopes = S.hlo_scopes(SHARED)
    assert scopes["sort.2"] == "jit(f)/ivec_moments/gather"
    assert scopes["iota.1"] == "jit(f)/ivec_estep/iota"


@pytest.mark.parametrize("scope,seconds", [
    ("ivec_preselect", 2.0),          # fusion.1 [0, 2]
    ("ivec_estep", 2.5),              # the two kernel ops [5, 7.5]
    ("ivec_mstep", 2.0 + 0.5),        # fusion.2 and the custom call
    ("ivec_sigma_floor", 2.0),        # nested inside the M-step
    ("ivec_rescore", 0.0),
    ("ivec_", 0.0)])                  # components, not prefixes
def test_scope_seconds_sums_the_ops_under_a_scope(scope, seconds):
    assert S.scope_seconds(_scoped(), scope) == pytest.approx(seconds)


def test_unstaged_seconds_counts_op_time_under_no_stage():
    # ops inside the window: copy.3 (1 s inside, scoped but under no
    # stage); everything else is under a stage
    assert S.unstaged_seconds(_scoped()) == pytest.approx(1.0)
    assert S.unstaged_seconds(S.with_scopes(_trace(), {})) == \
        pytest.approx(7.5)
    assert S.unstaged_seconds(T.Trace([], [], (0.0, 1.0))) == 0.0


def test_the_existing_reduction_reads_a_scoped_trace_as_before():
    plain, scoped = _trace(), _scoped()
    scoped.devices[0].pop()            # the op _scoped adds, last
    assert T.busy_seconds(scoped) == T.busy_seconds(plain)
    assert T.idle_share(scoped) == T.idle_share(plain)
    assert T.op_seconds(scoped, r"_matmul_kernel") == \
        T.op_seconds(plain, r"_matmul_kernel")
    assert T.top_ops(scoped) == T.top_ops(plain)
    # gaps are still named by the bench.* spans alone
    assert T.idle_gaps(scoped) == T.idle_gaps(plain)


STEP = """HloModule jit_iter_fn, is_scheduled=true
ENTRY %main.9 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %custom-call.1 = (f32[4]) custom-call(%x.1), custom_call_target="TopK", metadata={op_name="jit(iter_fn)/while/body/ivec_preselect/top_k"}
  %gmm_rescore.2 = f32[4]{0} custom-call(%x.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(iter_fn)/while/body/ivec_rescore/jit(gmm_rescore)/gmm_rescore/pallas_call"}
  %fusion.3 = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fc, metadata={op_name="jit(iter_fn)/while/body/ivec_moments/mul"}
  %tvm_estep_l.4 = f32[4]{0} custom-call(%x.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(iter_fn)/while/body/ivec_estep/jit(tvm_estep_l)/pallas_call"}
  %custom-call.5 = (f32[4]) custom-call(%x.1), custom_call_target="EighTpu", metadata={op_name="jit(iter_fn)/ivec_mstep/ivec_sigma_floor/jit(eigh)/eigh"}
  %custom-call.6 = (f32[4]) custom-call(%x.1), custom_call_target="LuDecompositionBlock", metadata={op_name="jit(iter_fn)/ivec_mstep/jit(solve)/lu"}
  ROOT %copy.7 = f32[4]{0} copy(%x.1)
}
"""
# another program that shares an instruction name with the step
OTHER = """HloModule jit_refresh, is_scheduled=true
ENTRY %main.2 (x.1: f32[4]) -> f32[4] {
  ROOT %fusion.3 = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fc, metadata={op_name="jit(refresh)/ivec_estep/mul"}
}
"""
PLAIN = STEP.replace("ivec_", "")


def _step_trace():
    """Two traced iterations of STEP; one op past the window."""
    E = T.Event
    ops = [E("custom-call.1:TopK", 0.0, 1.0), E("gmm_rescore.2", 1.0, 3.0),
           E("fusion.3", 3.0, 3.5), E("tvm_estep_l.4", 3.5, 4.0),
           E("custom-call.5:EighTpu", 4.0, 5.0),
           E("custom-call.6:LuDecompositionBlock", 5.0, 5.5),
           E("copy.7", 5.5, 6.0), E("fusion.3", 10.0, 11.0)]
    return T.Trace([ops], [E("bench.window", 0.0, 10.0)], (0.0, 10.0))


def test_the_program_that_ran_is_the_one_covering_the_window():
    tr = _step_trace()
    sure, unsure = S.program_scopes(tr, [OTHER, STEP])
    assert {**sure, **unsure} == S.hlo_scopes(STEP)
    # OTHER gives fusion.3 another scope: which program ran it is unsure
    assert unsure == {"fusion.3": S.hlo_scopes(STEP)["fusion.3"]}
    sure, unsure = S.program_scopes(tr, [OTHER])
    assert sure["fusion.3"].endswith("ivec_estep/mul") and not unsure
    assert S.program_scopes(tr, [PLAIN]) == ({}, {})
    assert S.program_scopes(tr, []) == ({}, {})
    # a module that names the step's ops alike leaves them sure
    assert S.program_scopes(tr, [STEP, STEP])[1] == {}


MOMENTS = S.hlo_scopes(STEP)["fusion.3"]


@pytest.mark.parametrize("names,joined", [
    (["gmm_rescore.2", "fusion.3", "tvm_estep_l.4"], 1.0),
    (["gmm_rescore.2", "fusion.3", "fusion.3", "tvm_estep_l.4"], 2.0),
    (["gmm_rescore.2", "multiply.9", "fusion.3", "tvm_estep_l.4"], 0.0),
    (["gmm_rescore.2", "fusion.3", "multiply.9", "tvm_estep_l.4"], 0.0),
    (["fusion.3", "gmm_rescore.2"], 0.0),
    (["gmm_rescore.2", "fusion.3"], 0.0)])
def test_an_unsure_op_is_joined_only_between_the_programs_own(names,
                                                             joined):
    """Ops 1 s each, in this order (listed in reverse: the order of the
    list does not matter, their times do); ``multiply.9`` is no op of
    the step."""
    E = T.Event
    ops = [E(n, float(i), i + 1.0) for i, n in enumerate(names)][::-1]
    tr = T.Trace([ops], [], (0.0, 10.0))
    sure, unsure = S.program_scopes(tr, [OTHER, STEP])
    st = S.with_scopes(tr, sure, unsure)
    assert [e.name for e in st.devices[0]] == [e.name for e in ops]
    assert S.scope_seconds(st, "ivec_moments") == pytest.approx(joined)
    assert all(e.scope in ("", MOMENTS) for e in st.devices[0]
               if e.name == "fusion.3")


def test_live_executables_give_the_hlo_of_a_program_that_ran():
    @jax.jit
    def f(x):
        with jax.named_scope("ivec_estep"):
            return jnp.sin(x) * 3.0
    f(jnp.ones((8,), jnp.float32)).block_until_ready()
    texts = S.live_hlo_texts()
    assert any("jit(f)/ivec_estep/" in t for t in texts)


READERS = [("preselect_ms.train", 500.0), ("rescore_ms.train", 1000.0),
           ("moments_ms.train", 250.0), ("estep_ms.train", 250.0),
           ("mstep_ms.train", 750.0),        # eigh 1 s and LU 0.5 s
           ("unstaged_ms.train", 250.0)]     # copy.7


def _reading(trace, iterations=2):
    return harness.Reading(None, {}, trace, {"iterations": iterations},
                           {})


@pytest.mark.parametrize("metric,ms", READERS)
def test_a_stage_reader_reads_device_ms_per_iteration(monkeypatch, metric,
                                                      ms):
    monkeypatch.setattr(S, "live_hlo_texts", lambda: [OTHER, STEP])
    read = harness.metric_reader(metric).read
    assert read(_reading(_step_trace())) == pytest.approx(ms)


@pytest.mark.parametrize("metric", [m for m, _ in READERS])
def test_a_stage_reader_reads_nothing_it_cannot_see(monkeypatch, metric):
    read = harness.metric_reader(metric).read
    monkeypatch.setattr(S, "live_hlo_texts", lambda: [PLAIN])
    assert read(_reading(_step_trace())) is None     # a program unscoped
    monkeypatch.setattr(S, "live_hlo_texts", lambda: [STEP])
    assert read(_reading(None)) is None
    assert read(_reading(T.Trace([], [], (0.0, 1.0)))) is None
    assert read(_reading(_step_trace(), iterations=0)) is None


def test_the_stages_and_the_rest_add_up_to_the_device_time(monkeypatch):
    monkeypatch.setattr(S, "live_hlo_texts", lambda: [OTHER, STEP])
    r = _reading(_step_trace(), iterations=1)
    total = sum(harness.metric_reader(m).read(r) for m, _ in READERS)
    assert total == pytest.approx(1e3 * T.busy_seconds(r.trace))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("stages"))


@pytest.mark.parametrize("requests,batches,share", [
    (448, 46, 100 * 448 / (46 * 4)), (4, 1, 100.0), (1, 1, 25.0),
    (0, 0, None)])
def test_batch_occupancy_is_requests_over_batch_rows(root, requests,
                                                     batches, share):
    cell = harness.load_cell(root, "serve.tiny.open")     # max_batch 4
    r = harness.Reading(cell, {}, None, {"requests": requests,
                                         "batches": batches}, {})
    got = harness.metric_reader("batch_occupancy.serve").read(r)
    assert got == (None if share is None else pytest.approx(share))


def test_batch_occupancy_reads_a_tiny_serving_window(root):
    """The counters ``bench/drivers/serve.py`` returns over a traced
    window: every request took one row of a batch of at most max_batch."""
    from bench.drivers import serve
    cell = harness.load_cell(root, "serve.tiny.open")
    state = serve.prepare(cell, 3, 0.5, lambda m: None)
    counters = serve.traced_window(state, lambda m: None)
    serve.release(state)
    assert counters["served"] == counters["requests"] > 0
    r = harness.Reading(cell, {}, None, counters, serve.shapes(state))
    got = harness.metric_reader("batch_occupancy.serve").read(r)
    assert 0.0 < got <= 100.0
    assert counters["batches"] * 4 * got / 100 == pytest.approx(
        counters["requests"])
