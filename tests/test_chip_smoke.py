"""CPU rehearsal of ``chip_smoke.py``: its phases at reduced width, kernels
in interpret mode, and its refusal to run anywhere but on a TPU."""
from __future__ import annotations

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_refuses_cpu(smoke):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as exc:
        smoke.require_tpu()
    assert exc.value.code not in (0, None)


def test_main_refuses_cpu_before_any_work(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_served_path_phase_reduced_width(smoke):
    problem = smoke.build_problem(d_model=8)
    assert problem.cfg.d_model == 8 and problem.cfg.n_layers == 2
    facts = smoke.served_path(problem, "cpu")
    assert facts["updates"] == 32
    assert facts["race_finals"] == [32, 32, 32]
    # the repo's CPU invariant: the served model bit-matches the reference
    assert facts["max_abs_diff"] == 0.0


def test_full_width_problem_is_the_paper_model(smoke):
    problem = smoke.build_problem()
    assert (problem.cfg.n_layers, problem.cfg.d_model) == (2, 50)
    assert problem.cfg.vocab == 38
    assert problem.tp.mini_batch_size == 8
    assert problem.tp.mini_batches_to_accumulate == 16
    assert problem.tp.sample_len == 40
    n_params = sum(x.size for x in jax.tree.leaves(problem.params0))
    assert n_params == 39_938


@pytest.mark.parametrize("label,name,shape", [
    ("lstm_cell paper", "lstm_cell", dict(B=8, Din=38, H=50)),
    ("lstm_cell 2 tiles", "lstm_cell", dict(B=16, Din=64, H=256)),
    ("rmsnorm", "rmsnorm", dict(rows=512, D=256)),
    ("flash_attention", "flash_attention",
     dict(B=1, S=256, H=8, Kv=4, hd=128)),
    ("ternary", "ternary", dict(n=4096)),
])
def test_kernel_phase_interpret(smoke, label, name, shape):
    [(got_label, err)] = smoke.kernels([(label, name, shape)],
                                       interpret=True)
    assert got_label == label and err < 2e-2


def test_kernel_phase_fails_on_wrong_output(smoke, monkeypatch):
    monkeypatch.setitem(smoke._CASES, "rmsnorm", lambda rows, D: (
        lambda x, s, *, interpret: x, smoke.ref.rmsnorm,
        (jnp.ones((rows, D), jnp.bfloat16), jnp.full((D,), 3.0,
                                                      jnp.bfloat16))))
    with pytest.raises(AssertionError):
        smoke.kernels([("rmsnorm", "rmsnorm", dict(rows=8, D=128))],
                      interpret=True)


def test_real_width_cases_cover_every_kernel(smoke):
    cases = smoke.real_width_cases(38)
    assert {name for _, name, _ in cases} == set(smoke._CASES)
    assert ("lstm_cell paper", "lstm_cell",
            dict(B=8, Din=38, H=50)) in cases
