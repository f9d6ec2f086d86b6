"""``chip_smoke.py`` rehearsed on the CPU at a toy size — the flow
(train -> export -> serve -> compare, and the ``--chips 4`` path on
virtual devices), the refusal to pass off a TPU, and the compile-cache
helper every entry point shares.  The chip run itself is
``python chip_smoke.py`` through the chip tool; nothing here measures
anything."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

#: Two blocks of width 32: seconds on the CPU.  The head dim (16) is
#: outside the flash kernel's contract and the platform is not a TPU,
#: so the script must find — and accept — the XLA attention path.
TOY = dict(vocab=64, seq=32, embed=32, heads=2, blocks=2, batch=4,
           ticks=2, train_blocks=2, first_loss=4.16)  # ln(64)


@pytest.fixture
def toy(monkeypatch):
    from veles_tpu.config import root, get
    engine = root.common.engine
    saved = {name: get(getattr(engine, name), None)
             for name in ("backend", "remat")}
    monkeypatch.setattr(chip_smoke, "SERVE_FLAGS",
                        ("--max-batch", "2", "--kv-block-size", "8"))
    monkeypatch.setattr(chip_smoke, "PROMPT_LENGTHS", (3, 9, 17, 9))
    monkeypatch.setattr(chip_smoke, "MAX_NEW_TOKENS", 4)
    yield TOY
    for name, value in saved.items():
        setattr(engine, name, value)


def _lines(capsys):
    return [json.loads(line) for line in
            capsys.readouterr().out.splitlines() if line.startswith("{")]


def test_train_export_serve_compare_at_toy_size(toy, tmp_path, capsys):
    chip_smoke.run(1, geometry=toy, backend="cpu",
                   scratch=str(tmp_path))
    phases = {line["phase"]: line for line in _lines(capsys)}
    assert phases["train"]["dispatches"] == 3
    assert phases["train"]["nonfinite_ticks"] == 0
    assert phases["train.compiled_step"]["attention_path"] == "xla"
    assert phases["export"]["artifact_bytes"] > 0
    assert phases["serve.requests"]["device"]["platform"] == "cpu"
    assert phases["serve.requests"]["counters"]["warmup.compiles"] > 0
    assert phases["serve.local_generate"]["tokens_equal"] is True
    assert phases["serve.logits_vs_forward_numpy"]["close"] is True


def test_chips_4_path_on_virtual_devices(toy, capsys):
    """The data-parallel phase and what it is compared with, and no
    other phase: batch blocks split four ways INSIDE the compiled
    block program, parameters on every device, an all-reduce."""
    chip_smoke.run(4, geometry=toy, backend="cpu")
    lines = _lines(capsys)
    phases = {line["phase"] for line in lines}
    assert phases == {"start", "dp.one_chip", "dp.four_chips",
                      "dp.layout", "dp.compare", "free"}
    layout = next(l for l in lines if l["phase"] == "dp.layout")
    assert layout["chips"] == 4 and layout["all_reduces"] > 0
    assert layout["block_shard_shapes"] == {"(2, 4)": [2, 1]}
    assert all(l["close"] for l in lines if l["phase"] == "dp.compare")


def test_a_failed_check_fails_the_run(toy, tmp_path, monkeypatch):
    """No phase's failure is swallowed: a loss outside the bound
    raises out of ``run``."""
    monkeypatch.setattr(chip_smoke, "LOSS_BOUND", 0.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="first loss"):
        chip_smoke.run(1, geometry=toy, backend="cpu",
                       scratch=str(tmp_path))


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_exits_nonzero_off_tpu(argv, capsys):
    """On the CPU the command fails, and says so in its last line."""
    assert chip_smoke.main(argv) != 0
    last = _lines(capsys)[-1]
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "not on a TPU" in last["error"]


def test_compile_cache_is_placed_by_the_environment(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no
    code sets a directory."""
    import jax
    from veles_tpu import backends
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    assert backends.enable_compilation_cache() == "/some/where"
    assert updates == []


def test_compile_cache_defaults_to_one_path_in_the_checkout(
        monkeypatch):
    import jax
    from veles_tpu import backends
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert backends.COMPILE_CACHE_DIR == os.path.join(repo,
                                                      ".jax_cache")
    assert backends.enable_compilation_cache() == \
        backends.COMPILE_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == \
        backends.COMPILE_CACHE_DIR
    with open(os.path.join(repo, ".gitignore")) as fin:
        assert ".jax_cache/" in fin.read().split()
