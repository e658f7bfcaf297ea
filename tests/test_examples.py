"""Example-as-smoke-test — the reference CI sed-shrinks and runs its real
examples under ``mpirun -np 2`` (.travis.yml:113-157). Here each example
runs as a real subprocess on the virtual CPU mesh with shrunken step
counts; pass criterion is exit 0 plus the expected progress output.

Marked slow: each example pays interpreter + jax startup (~20-60 s).
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def _needs(module):
    """Skip when the example's framework isn't installed — the same
    importorskip convention the unit suites use (tests/test_keras.py:14).
    Examples run as subprocesses, so importorskip alone can't gate them."""
    pytest.importorskip(module)


# Names of examples that needed their retry this run. One or two
# scheduling hiccups on a shared box are expected noise; more means the
# retry is masking genuine flakiness — fail the run so "suite green"
# keeps meaning something.
_retries_used = []
_MAX_RETRIES_PER_RUN = 2


@pytest.fixture(scope="module", autouse=True)
def _retry_budget():
    yield
    assert len(_retries_used) <= _MAX_RETRIES_PER_RUN, (
        f"{len(_retries_used)} examples needed their retry this run "
        f"({', '.join(_retries_used)}) — above the "
        f"{_MAX_RETRIES_PER_RUN}-retry noise budget; the retry is "
        "masking real flakiness, investigate instead of re-running")


def _run(name, env_extra=None, args=(), timeout=420, devices=8):
    env = dict(os.environ)
    # Other test modules set KERAS_BACKEND at import (collection) time;
    # examples must see a clean slate and choose their own backend.
    env.pop("KERAS_BACKEND", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "STEPS": "8", "EPOCHS": "1",
    })
    env.update(env_extra or {})
    # One retry: these spawn full framework subprocesses on a shared
    # 1-core box, where XLA's 40 s collective-rendezvous skew timeout
    # occasionally trips under full-suite load. A deterministic breakage
    # still fails twice; a scheduling hiccup passes on the second try.
    details = []
    for _ in (0, 1):
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(EXAMPLES, name), *args],
                capture_output=True, text=True, timeout=timeout, env=env,
                cwd=EXAMPLES)
        except subprocess.TimeoutExpired as e:
            def _txt(b):
                return (b.decode() if isinstance(b, bytes) else (b or ""))
            details.append(f"timed out after {timeout}s\n"
                           f"stdout:\n{_txt(e.stdout)[-2000:]}\n"
                           f"stderr:\n{_txt(e.stderr)[-2000:]}")
            continue  # a hang is the same flake class as a crash
        if proc.returncode == 0:
            if details:  # first attempt failed, retry saved it
                _retries_used.append(name)
            return proc.stdout
        details.append(f"exit {proc.returncode}\n"
                       f"stdout:\n{proc.stdout[-2000:]}\n"
                       f"stderr:\n{proc.stderr[-2000:]}")
    pytest.fail(f"{name} failed twice:\n--- attempt 1 ---\n{details[0]}\n"
                f"--- attempt 2 ---\n{details[1]}")


class TestExamples:
    def test_jax_mnist(self):
        out = _run("jax_mnist.py")
        assert "loss" in out and "checkpoint written" in out

    def test_jax_mnist_file_data(self, tmp_path):
        """Rank-sharded FILE-reading input pipeline: the
        example must genuinely read per-rank shard files from disk."""
        out = _run("jax_mnist_file_data.py",
                   {"DATA_DIR": str(tmp_path / "shards"), "STEPS": "8"})
        assert "reading" in out and "shard files" in out
        assert "loss" in out and "done:" in out
        import glob as _g
        assert len(_g.glob(str(tmp_path / "shards" / "*.npz"))) == 8

    def test_jax_pipeline_end_to_end(self, tmp_path):
        """The full-pipeline example (the reference's
        keras_spark_rossmann.py scope): ETL -> rank-sharded train ->
        rank-0 checkpoint -> restore/resume -> inference writing a
        predictions file. PIPELINE_OK prints only if the resumed loss
        continued descending AND holdout RMSE reached the noise floor."""
        data = tmp_path / "pipeline"
        out = _run("jax_pipeline_end_to_end.py",
                   {"DATA_DIR": str(data), "STEPS": "25", "EPOCHS": "2",
                    "N_ROWS": "8000"}, devices=1)
        assert "[etl]" in out  # 'wrote' first run, 'reusing' on retry
        assert "[resume] restored" in out
        assert "PIPELINE_OK" in out
        assert (data / "predictions.csv").exists()
        assert (data / "checkpoints" / "2.pkl").exists()

    def test_jax_mnist_eager(self):
        # 2 virtual devices: the eager fused collective rendezvous has a
        # 40 s skew timeout, and 8 conv workloads sharing one CPU core
        # can exceed it (real meshes have a core per device).
        out = _run("jax_mnist_eager.py", {"STEPS": "4"}, devices=2)
        assert "loss" in out

    def test_jax_word2vec(self):
        out = _run("jax_word2vec.py", {"STEPS": "30"})
        assert "nce loss" in out and "nearest" in out

    def test_pytorch_mnist(self):
        _needs("torch")
        out = _run("pytorch_mnist.py")
        assert "acc" in out

    def test_mxnet_mnist(self):
        out = _run("mxnet_mnist.py")
        assert "acc" in out

    def test_mxnet_imagenet_resnet50(self):
        out = _run("mxnet_imagenet_resnet50.py",
                   args=("--batch-size", "2", "--image-size", "32"))
        assert "loss" in out

    def test_pytorch_imagenet_resnet50(self):
        _needs("torch")
        out = _run("pytorch_imagenet_resnet50.py",
                   args=("--epochs", "1", "--batch-size", "2",
                         "--image-size", "32",
                         "--batches-per-allreduce", "2"))
        assert "epoch 0" in out

    def test_tensorflow_mnist(self):
        _needs("tensorflow")
        # 2 devices: TF + JAX on one CPU core is contention-flaky at 8
        # (same reasoning as test_jax_mnist_eager).
        out = _run("tensorflow_mnist.py", {"STEPS": "6"}, devices=2)
        assert "loss" in out and "checkpoint written" in out

    def test_pytorch_synthetic_benchmark(self):
        _needs("torch")
        out = _run("pytorch_synthetic_benchmark.py",
                   args=("--model", "resnet18", "--batch-size", "2",
                         "--image-size", "32", "--num-iters", "1",
                         "--num-batches-per-iter", "1",
                         "--num-warmup-batches", "1"))
        assert "Img/sec" in out

    def test_runner_end_to_end(self):
        out = _run("runner_end_to_end.py",
                   {"NP": "2",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
        assert "rank 0" in out and "rank 1" in out
        assert "sample predictions" in out

    def test_tensorflow_mnist_eager(self):
        _needs("tensorflow")
        out = _run("tensorflow_mnist_eager.py", {"STEPS": "6"}, devices=2)
        assert "loss" in out

    def test_tensorflow_mnist_estimator(self):
        _needs("tensorflow")
        out = _run("tensorflow_mnist_estimator.py", {"STEPS": "8"},
                   devices=2)
        assert "DONE" in out

    def test_keras_mnist(self):
        _needs("keras")
        _needs("torch")  # the example's default Keras backend
        out = _run("keras_mnist.py", timeout=600,
                   env_extra={"KERAS_BACKEND": "torch"})
        assert "accuracy" in out
