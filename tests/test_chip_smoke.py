"""``chip_smoke.py`` on CPU: its phase functions at a tiny size with
interpret-mode kernels (control flow and the checks that make it fail),
and its refusal to run without a TPU."""
import importlib.util
import pathlib

import numpy as np
import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    """A built 2,048-point SIFT-width index, its queries and truth."""
    x, queries = smoke.make_corpus(2048, 48, seed=0)
    index, wall = smoke.build_index(x, seed=0)
    assert wall > 0
    return index, x, queries, smoke.brute_force(x, queries)


@pytest.mark.parametrize("chips", [[], ["--chips", "4"]])
def test_main_refuses_a_non_tpu_device(smoke, capsys, chips):
    with pytest.raises(SystemExit) as exc:
        smoke.main(chips)
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_kernel_phase_tiny_interpret(smoke):
    diffs = smoke.kernel_phase(n_vmem=300, n_hbm=520, d=32, nq=9, c=40,
                               n_res=24, l_max=8, n_edges=1500,
                               interpret=True)
    assert set(diffs) == {
        "gather_distance", "gather_distance_int8", "gather_distance_hbm",
        "gather_distance_int8_hbm", "merge_sorted_reservoirs.ids",
        "merge_sorted_reservoirs.hashes", "merge_sorted_reservoirs.dists",
        "edge_hashes"}
    # interpret mode reproduces every oracle bit for bit
    assert all(v == 0.0 for v in diffs.values()), diffs


def test_check_accepts_rounding_and_rejects_mismatch(smoke):
    want = np.array([1.0, 2.0, np.inf], np.float32)
    assert smoke._check("ok", want, want) == 0.0
    assert smoke._check("close", want * np.float32(1 + 1e-7), want,
                        rtol=1e-6) > 0
    with pytest.raises(AssertionError, match="exceeds"):
        smoke._check("off", np.array([1.0, 2.5, np.inf]), want, rtol=1e-6)
    with pytest.raises(AssertionError, match="non-finite"):
        smoke._check("inf", np.array([1.0, 2.0, 3.0]), want)
    with pytest.raises(AssertionError, match="shape"):
        smoke._check("shape", want[:2], want)


def test_serve_phase_tiny_passes(smoke, tiny):
    index, x, queries, truth = tiny
    out = smoke.serve_phase(index, x, queries, truth, beams=(32, 64),
                            expect_path="xla")
    assert set(out) == {"f32", "int8"}
    assert out["f32"]["kernel_path"] == "xla"
    assert max(out["f32"]["recall"].values()) >= smoke.RECALL_BAR
    assert 0.0 <= min(out["int8"]["recall"].values()) <= 1.0


def test_serve_phase_fails_below_the_recall_bar(smoke, tiny):
    index, x, queries, truth = tiny
    wrong = np.roll(truth, 1, axis=0)        # every query gets another's
    with pytest.raises(AssertionError, match="recall"):
        smoke.serve_phase(index, x, queries, wrong, beams=(32,),
                          expect_path="xla")


def test_serve_phase_fails_on_an_unexpected_kernel_path(smoke, tiny):
    index, x, queries, truth = tiny
    with pytest.raises(AssertionError, match="kernel_path"):
        smoke.serve_phase(index, x, queries, truth, beams=(32,),
                          expect_path="hbm")


def test_serve_loop_pass_answers_every_request(smoke, tiny):
    from repro.core.serving import ServingIndex

    index, x, queries, truth = tiny
    sv = ServingIndex.from_index(index, x)
    ids, counters = smoke.serve_loop_pass(sv, queries, beam=32)
    assert ids.shape == (len(queries), 10)
    assert counters["served"] == len(queries)
    assert not counters.get("invalid") and not counters.get("timeout")
