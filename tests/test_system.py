"""End-to-end behaviour tests for the full system."""
import json
import os
import shutil
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_e2e_train_reduced_model(tmp_path):
    """Train a reduced model for a few steps via the real entry point."""
    from repro.configs import get_reduced_config
    from repro.data import DataConfig
    from repro.models import build_model
    from repro.training import OptimizerConfig, TrainConfig
    from repro.training.train_loop import LoopConfig, train_loop

    cfg = get_reduced_config("minicpm-2b")
    model = build_model(cfg)
    tc = TrainConfig(optimizer=OptimizerConfig(lr=2e-3, schedule="wsd",
                                               warmup_steps=3,
                                               total_steps=20),
                     accum_steps=2)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    out = train_loop(model, tc, dc,
                     LoopConfig(total_steps=20, ckpt_dir=str(tmp_path),
                                ckpt_every=10, log_every=5,
                                ), log=lambda *_: None)
    losses = [l for _, l in out["losses"]]
    assert losses[-1] < losses[0]
    # checkpoint was written and resume picks it up
    out2 = train_loop(model, tc, dc,
                      LoopConfig(total_steps=22, ckpt_dir=str(tmp_path),
                                 ckpt_every=10, log_every=1),
                      log=lambda *_: None)
    assert out2["losses"][0][0] >= 20


def test_e2e_multi_tenant_serving_sim():
    """Full multi-tenant pipeline: compile plans -> simulate -> metrics."""
    from repro.core import cost_model as cm
    from repro.core.qos import qps_at_qos
    from repro.core.scheduler import VeltairPolicy
    from repro.serving import Simulator, build_paper_plans, poisson_workload

    hw = cm.CPU_3990X
    plans = build_paper_plans(["resnet50", "googlenet"], hw)
    sweep = []
    for qps in (40, 80):
        sim = Simulator(hw, plans, VeltairPolicy(hw))
        m = sim.run(poisson_workload(["resnet50", "googlenet"], qps, 100,
                                     seed=0))
        sweep.append((qps, m))
    assert qps_at_qos(sweep, target=0.9) >= 40


@pytest.mark.slow
def test_dryrun_single_cell_subprocess(tmp_path):
    """The dry-run lowers+compiles a cell on the 512-device mesh.  Runs in
    a subprocess so XLA_FLAGS never pollute this test process."""
    out = tmp_path / "cell.jsonl"
    env = dict(os.environ, PYTHONPATH=SRC)
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "mamba2-780m", "--shape", "decode_32k", "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-2000:]
    rec = json.loads(out.read_text().splitlines()[-1])
    assert rec["status"] == "ok", rec
    assert rec["n_devices"] == 256
    assert rec["cost"].get("flops", 0) > 0


@pytest.mark.parametrize("alone", [False, True], ids=["cpu", "script-alone"])
def test_chip_smoke_refuses_without_tpu_or_repo(tmp_path, alone):
    """chip_smoke.py has no CPU path: on the CPU, or copied away from the
    repository, it exits non-zero with a one-line reason and prints no
    result line."""
    script = os.path.join(os.path.dirname(SRC), "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path)
    res = subprocess.run([sys.executable, script],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    reason = "no repro package" if alone else "no TPU"
    assert reason in res.stderr.strip().splitlines()[-1]


def test_lm_profiles_flops_sane():
    """GEMM-reduced profiles match closed-form 6ND within tolerance."""
    from repro.configs import get_config, get_shape
    from repro.core.profiles import model_flops
    from repro.models import build_model, param_count

    cfg = get_config("gemma-2b")
    shape = get_shape("train_4k")
    n_params = param_count(build_model(cfg).param_specs())
    tokens = shape.global_batch * shape.seq_len
    fwd = model_flops(cfg, shape)
    # forward-only ~= 2*N*D (+attention); allow wide band
    assert 1.5 * n_params * tokens < fwd < 5.0 * n_params * tokens
