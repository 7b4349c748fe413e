"""The trace reduction against a recorded trace: a cut of the profile of one
5-iteration ``train_ials`` job at the Netflix shape, rank 128, on one TPU v5
lite (my chip run, PR 23) — the three programs of the ``XLA Modules`` line
and 3,500 of the 151,822 ``XLA Ops`` events, names shortened."""

import os

import pytest

from benchmarks.harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "ials_r128_netflix_v5e.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.reduce_xplane(DATA)


def test_busy_time_is_the_union_of_the_programs(trace):
    assert trace.devices == 1
    assert [m[2].split("(")[0] for m in trace.modules[0]] == [
        "jit_convert_element_type", "jit__threefry_seed", "jit__train_loop"]
    assert trace.busy_s() == pytest.approx(6.528492786, rel=1e-9)
    assert trace.profile_start_unix_ns == 1790536537874784397
    assert trace.mark is None  # recorded before the harness marked its window


def test_kernels_are_found_by_name(trace):
    assert len(trace.ops) == 3500
    secs, n = trace.kernel_seconds(r"^_?gram_tiles")
    assert n == 220 and secs == pytest.approx(0.018229719, rel=1e-6)
    secs, n = trace.kernel_seconds(r"^_gauss_solve")
    assert n == 119 and secs == pytest.approx(0.050798053, rel=1e-6)
    assert trace.kernel_seconds(r"^_topk_call") == (0.0, 0)
    # a fusion is not a kernel, whatever its name matches
    assert trace.kernel_seconds(r"^fusion") == (0.0, 0)


def test_self_times_take_nested_ops_out(trace):
    st = trace.self_times()
    # the outer while wraps everything: what is left to it is the cut's gaps
    assert sum(st.values()) == pytest.approx(6.528489761, rel=1e-6)
    solves = sum(v for k, v in st.items()
                 if k.startswith("_gauss_solve_reg_pallas"))
    assert solves == pytest.approx(0.050798053, rel=1e-6)  # leaves keep it all
    assert max(st, key=st.get).startswith("while")


def test_op_token_and_gap_naming(trace):
    assert xplane.op_token(
        "%_gauss_solve_reg_pallas.16 = f32[227,128]{1,0} custom-call(...)"
    ) == "_gauss_solve_reg_pallas.16"
    assert xplane.op_token("jit__topk_call(123)") == "jit__topk_call(123)"
    lo, hi = 0.0, 7.0
    gaps = xplane.name_gaps(trace, lo, hi, [(0.0, 7.0, "bench/job"),
                                            (0.1, 0.3, "upload")], top=2)
    assert gaps[0][0] == "upload"  # the 0.065 -> 0.353 s gap before the loop
    assert gaps[0][1] == pytest.approx(0.353025 - 0.065295, abs=1e-4)
    assert gaps[1][0] == "bench/job"
