"""chip_smoke.py's contract, as far as a CPU can hold it: it never passes
off the TPU, a phase that raises ends non-zero with a failing last line,
the passing line is exactly the contract's, and the rehearsal walks every
phase at a tiny size (the path the chip run takes at the real one)."""
import json
import os

import jax
import pytest

import chip_smoke as cs
from paddle_tpu.utils import compile_cache

PASSING = {"ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                                  "count": 1}}


def _run(capsys, argv):
    rc = cs.main(argv)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return rc, lines


@pytest.fixture
def stub_phases(monkeypatch):
    for name in ("phase_train", "phase_serve", "phase_mesh_train"):
        monkeypatch.setattr(cs, name, lambda args, dev: {"stub": True})


@pytest.fixture(autouse=True)
def restore_cache_dir():
    """main() and the cache helpers point jax's cache somewhere."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_no_tpu_fails_at_once(capsys):
    rc, lines = _run(capsys, [])
    assert rc != 0
    assert [ln.get("phase") for ln in lines] == ["start", None]
    assert lines[-1]["ok"] is False and "no TPU" in lines[-1]["error"]


def test_raising_phase_ends_nonzero(capsys, monkeypatch, stub_phases):
    def boom(args, dev):
        raise RuntimeError("injected")

    monkeypatch.setattr(cs, "phase_serve", boom)
    monkeypatch.setattr(cs, "device_info", lambda: PASSING["device"])
    rc, lines = _run(capsys, [])
    assert rc != 0
    assert lines[-1]["ok"] is False
    assert lines[-1]["failed_phase"] == "serve"
    assert "injected" in lines[-1]["error"]
    assert [ln["phase"] for ln in lines[:-1]] == ["start", "train"]


def test_passing_line_is_the_contracts(capsys, monkeypatch, stub_phases):
    monkeypatch.setattr(cs, "device_info", lambda: PASSING["device"])
    rc, lines = _run(capsys, [])
    assert rc == 0 and lines[-1] == PASSING
    assert [ln["phase"] for ln in lines[:-1]] == ["start", "train", "serve"]


def test_chips_4_runs_only_the_mesh_phase(capsys, monkeypatch, stub_phases):
    dev = {**PASSING["device"], "count": 4}
    monkeypatch.setattr(cs, "device_info", lambda: dev)
    rc, lines = _run(capsys, ["--chips", "4"])
    assert rc == 0 and lines[-1] == {"ok": True, "device": dev}
    assert [ln["phase"] for ln in lines[:-1]] == ["start", "mesh_train"]
    # one chip asked for, four found: refuse before any phase
    rc, lines = _run(capsys, [])
    assert rc != 0 and [ln.get("phase") for ln in lines] == ["start", None]


@pytest.mark.parametrize("argv,phases", [
    (["--rehearse"], ["train", "serve"]),
    (["--rehearse", "--chips", "4"], ["mesh_train"])])
def test_rehearsal_walks_every_phase_and_never_passes(capsys, argv, phases):
    rc, lines = _run(capsys, argv)
    assert rc != 0 and lines[-1]["ok"] is False
    assert lines[-1]["failed_phase"] is None, lines[-1]
    assert [ln["phase"] for ln in lines[1:-1]] == phases
    assert all(ln["ok"] for ln in lines[1:-1])
    if "serve" in phases:
        serve = lines[2]
        assert serve["compared_in"] == "float32"    # both passes walked
        facts = serve["float32"]["paged"]
        assert facts["tokens_equal_llama_generate"] == "64/64"


class TestServedLogitGap:
    """A served token that differs from llama_generate's is held to the
    benchmark's measure (ISSUE 28: the decode kernel's online softmax may
    flip a near-tie); a wrong token reads far over the limit."""

    @pytest.fixture(scope="class")
    def served(self):
        from paddle_tpu.models.llama import llama_init_params
        cfg = cs.model_config(True, 2)
        params = llama_init_params(cfg, jax.random.PRNGKey(0))
        requests = cs.make_requests(cfg, 0, True)[:2]
        return cfg, params, requests, cs.reference_tokens(cfg, params,
                                                          requests)

    def test_equal_tokens_are_not_looked_at(self, served):
        cfg, params, requests, ref = served
        assert cs.served_logit_gap(cfg, params, requests, ref, ref) == 0.0

    def test_greedy_tokens_read_zero_against_another_reference(self, served):
        """The greedy tokens ARE the best at every position: held against
        a reference that differs, their own gap is 0."""
        cfg, params, requests, ref = served
        other = [[(t + 1) % cfg.vocab_size for t in out] for out in ref]
        assert cs.served_logit_gap(cfg, params, requests, ref, other) < 1e-5

    def test_a_wrong_token_reads_over_the_limit(self, served):
        cfg, params, requests, ref = served
        wrong = [list(out) for out in ref]
        wrong[1][3] = (wrong[1][3] + 7) % cfg.vocab_size
        gap = cs.served_logit_gap(cfg, params, requests, wrong, ref)
        assert gap > cs.SERVE_LOGIT_GAP_LIMIT


class TestCompileCachePlacement:
    def test_env_places_the_cache_and_code_sets_no_path(
            self, monkeypatch, tmp_path):
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None

    def test_default_is_a_fixed_dir_inside_the_checkout(
            self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_CACHE_DIR, raising=False)
        path = compile_cache.enable_compile_cache()
        root = os.path.dirname(os.path.abspath(cs.__file__))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path

    def test_warmstart_yields_to_the_variable(self, monkeypatch, tmp_path,
                                              capsys):
        from paddle_tpu.inference.warmstart import enable_jit_cache
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path / "env"))
        enable_jit_cache(str(tmp_path / "own"))
        assert jax.config.jax_compilation_cache_dir is None
        assert not (tmp_path / "own").exists()
        assert "is set" in capsys.readouterr().err
