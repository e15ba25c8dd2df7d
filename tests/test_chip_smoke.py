"""``chip_smoke.py`` off the chip: its phases at ``.reduced()`` widths on
the CPU (Pallas kernels in the interpreter), and its refusal to run, or
print a result, without a TPU."""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

import chip_smoke

ROOT = Path(__file__).resolve().parents[1]
GEN = 12                    # > rebalance_every: a rebalance must fire


@pytest.fixture(scope="module")
def small():
    cfg = chip_smoke.smoke_config().reduced()
    prompts = chip_smoke.make_prompts(cfg, chip_smoke.N_REQUESTS, 16, 48)
    return cfg, prompts, chip_smoke.smoke_engine_config(48, GEN)


@pytest.mark.parametrize("argv", [[], ["--four-chips"]],
                         ids=["one-chip", "four-chips"])
def test_main_refuses_without_tpu(argv, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "needs a TPU" in out.err


@pytest.mark.parametrize("algo", ["metro", "eplb"])
def test_serve_phase_reduced(small, algo, capsys):
    cfg, prompts, ecfg = small
    chip_smoke.serve_phase(cfg, dataclasses.replace(ecfg, decode_algo=algo),
                           prompts, GEN, algo)
    assert f"completed {len(prompts)}/{len(prompts)}" in capsys.readouterr().out


def test_kernel_phase_reduced(small):
    cfg, prompts, ecfg = small
    assert chip_smoke.kernel_phase(cfg, ecfg, prompts, GEN) \
        <= chip_smoke.LOGIT_RTOL


def test_compiled_kernels_names_only_pallas_calls():
    text = "\n".join([
        '  %fused_expert_ffn_pallas.9 = bf16[8,2048]{1,0} custom-call(), '
        'custom_call_target="tpu_custom_call"',
        '  %ragged-dot-none = bf16[8,1536]{1,0} custom-call(), '
        'custom_call_target="tpu_custom_call"',
        '  %flash_decode_paged.3 = bf16[8,4,8,128] fusion(%metro_route_'
        'pallas.1)'])
    assert chip_smoke.compiled_kernels(text) == {"fused_expert_ffn_pallas"}


EP_SCRIPT = textwrap.dedent("""
    import jax, chip_smoke
    rel = chip_smoke.ep_layer_phase(chip_smoke.smoke_config().reduced(),
                                    jax.devices())
    assert len(rel) == 4, rel
    print("EP_PHASE_OK")
""")


def test_ep_layer_phase_reduced():
    """The four-chip phase on four virtual CPU devices (the device
    count must be set before JAX starts, hence the subprocess)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    out = subprocess.run([sys.executable, "-c", EP_SCRIPT], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=600)
    assert "EP_PHASE_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]


def test_fails_outside_the_repo(tmp_path):
    """Alone in a directory, the script cannot import the program and
    exits nonzero with no result line."""
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0 and '"ok"' not in out.stdout
