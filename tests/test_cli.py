"""bigdl-tpu launcher specs (the bigdl-submit analog, SURVEY §2 CLI row)."""

import os
import subprocess
import sys
import textwrap


def _repo_env():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [repo, env.get("PYTHONPATH")] if p)
    return env


def test_cli_run_single_process(tmp_path):
    script = tmp_path / "job.py"
    script.write_text(textwrap.dedent("""
        import sys
        print("ARGS", sys.argv[1:])
    """))
    out = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.cli", "run", str(script),
         "--alpha", "2"],
        env=_repo_env(), capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "ARGS ['--alpha', '2']" in out.stdout


def test_cli_run_local_gang_rendezvous(tmp_path):
    """-n 2 spawns a local gang whose members rendezvous through
    jax.distributed — the local-cluster launch mode."""
    script = tmp_path / "job.py"
    script.write_text(textwrap.dedent("""
        import jax
        jax.config.update("jax_platforms", "cpu")
        from bigdl_tpu.runtime.engine import init_engine
        init_engine()
        print(f"RANK{jax.process_index()}/{jax.process_count()}")
    """))
    out = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.cli", "run", "-n", "2", "--cpu",
         str(script)],
        env=_repo_env(), capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "RANK0/2" in out.stdout and "RANK1/2" in out.stdout


def test_cli_propagates_child_failure(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text("raise SystemExit(3)")
    out = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.cli", "run", str(script)],
        env=_repo_env(), capture_output=True, text=True, timeout=120)
    assert out.returncode == 3


def test_cli_gang_kills_peers_when_one_rank_crashes(tmp_path):
    """ADVICE r2: one crashed rank must fail the gang FAST — survivors
    blocked forever (here: rank 0 sleeps 600s) are killed as soon as the
    crash is observed, not after their own wait() returns."""
    import time

    script = tmp_path / "job.py"
    script.write_text(textwrap.dedent("""
        import os, sys, time
        rank = int(os.environ["BIGDL_TPU_PROCESS_ID"])
        if rank == 1:
            sys.exit(7)
        time.sleep(600)   # simulates a peer stuck in rendezvous
    """))
    t0 = time.time()
    out = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.cli", "run", "-n", "2", "--cpu",
         str(script)],
        env=_repo_env(), capture_output=True, text=True, timeout=120)
    assert out.returncode == 7
    assert time.time() - t0 < 60     # fail-fast, not the 600s sleep


def test_cli_pack_npz_and_csv(tmp_path):
    import numpy as np

    np.savez(tmp_path / "d.npz", x=np.random.rand(10, 3).astype("float32"),
             y=np.arange(10, dtype="int32"))
    out = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.cli", "pack",
         str(tmp_path / "d.npz"), str(tmp_path / "d.btrec")],
        env=_repo_env(), capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr
    from bigdl_tpu.data.records import RecordDataSet

    ds = RecordDataSet(str(tmp_path / "d.btrec"))
    assert ds.size() == 10 and ds.label == "y"
    ds.close()

    import pandas as pd

    pd.DataFrame({"a": [1.0, 2.0], "b": [3.0, 4.0],
                  "label": [0, 1]}).to_csv(tmp_path / "d.csv", index=False)
    out = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.cli", "pack",
         str(tmp_path / "d.csv"), str(tmp_path / "c.btrec")],
        env=_repo_env(), capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr
    ds = RecordDataSet(str(tmp_path / "c.btrec"))
    mb = next(ds.batches(2, shuffle=False, drop_last=False))
    assert mb["input"].shape == (2, 2)
    ds.close()


def test_cli_doctor_reports_environment():
    env = _repo_env()
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.cli", "doctor"],
        env=env, capture_output=True, text=True, timeout=200)
    assert out.returncode == 0, out.stderr
    import json

    report = json.loads(out.stdout)
    assert report["backend"]["platform"] == "cpu"
    assert report["backend"]["n_devices"] == 8
    assert report["mesh"]["data"] == 8
    assert "available" in report["native_lib"]


def test_cli_doctor_honors_dcn_env_and_fails_on_bad_mesh():
    import json

    env = _repo_env()
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["BIGDL_TPU_DCN_SLICES"] = "2"
    out = subprocess.run([sys.executable, "-m", "bigdl_tpu.cli", "doctor"],
                         env=env, capture_output=True, text=True,
                         timeout=200)
    report = json.loads(out.stdout)
    assert report["mesh"] == {"dcn_data": 2, "data": 4, "model": 1,
                              "seq": 1, "expert": 1, "pipe": 1}
    assert out.returncode == 0

    env["BIGDL_TPU_DCN_SLICES"] = "3"   # 8 devices not divisible by 3
    out = subprocess.run([sys.executable, "-m", "bigdl_tpu.cli", "doctor"],
                         env=env, capture_output=True, text=True,
                         timeout=200)
    report = json.loads(out.stdout)
    assert "error" in report["mesh"]
    assert out.returncode == 1
