"""JAX's compile events as the registry's counters by program
(``registry.watch_compiles``) and the set-up spans ``hvd/init`` and
``hvd/step/make`` (docs/tracing.md#names).

The registry holds process totals with no reset, so each test reads the
difference of two snapshots, over a program of its own name."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import transformer as tfm
from horovod_tpu.observability import registry as _reg
from horovod_tpu.parallel import create_mesh
from horovod_tpu.parallel.train import (build_pipeline_train_step,
                                        build_train_step, to_pipeline_params)

ROOT = Path(__file__).resolve().parent.parent
SECONDS = "hvdtpu_compile_seconds_total"
PROGRAMS = "hvdtpu_compile_programs_total"
SETUP = "hvdtpu_setup_seconds_total"


def _values(family):
    return dict(_reg.snapshot(prefix=family).get(family, {})
                .get("values", {}))


def _of(family, program):
    """``{other label: value}`` of one program's children."""
    tail = f',program="{program}"'
    return {key[:-len(tail)]: v for key, v in _values(family).items()
            if key.endswith(tail)}


def test_a_jitted_function_adds_its_three_phases_once():
    def counted_three_phases(x):
        return jnp.sin(x) * 2 + 1

    f = jax.jit(counted_three_phases)
    f(jnp.ones((5,))).block_until_ready()
    seconds = _of(SECONDS, "counted_three_phases")
    assert set(seconds) == {'phase="trace"', 'phase="lower"',
                            'phase="compile"'}
    assert all(v > 0 for v in seconds.values())
    assert sum(_of(PROGRAMS, "counted_three_phases").values()) == 1
    # the same shapes again: nothing is traced, lowered or compiled
    f(jnp.ones((5,))).block_until_ready()
    assert _of(SECONDS, "counted_three_phases") == seconds
    assert sum(_of(PROGRAMS, "counted_three_phases").values()) == 1


def test_a_function_jitted_inside_a_trace_is_the_outer_programs_time():
    def inner_of_a_nested_jit(x):
        return x * 3

    inner = jax.jit(inner_of_a_nested_jit)

    def outer_of_a_nested_jit(x):
        return inner(x) + 1

    jax.jit(outer_of_a_nested_jit)(jnp.ones((7,))).block_until_ready()
    assert set(_of(SECONDS, "outer_of_a_nested_jit")) == {
        'phase="trace"', 'phase="lower"', 'phase="compile"'}
    # traced inside the outer trace and compiled in its module: no
    # seconds and no compile of its own
    assert _of(SECONDS, "inner_of_a_nested_jit") == {}
    assert _of(PROGRAMS, "inner_of_a_nested_jit") == {}


def test_installing_twice_counts_once():
    _reg.watch_compiles()
    _reg.watch_compiles()

    def counted_after_two_installs(x):
        return x - 1

    jax.jit(counted_after_two_installs)(jnp.ones((3,))).block_until_ready()
    assert sum(_of(PROGRAMS, "counted_after_two_installs").values()) == 1


def test_nothing_is_recorded_while_metrics_are_off():
    def never_counted(x):
        return x * x

    _reg.set_enabled(False)
    try:
        jax.jit(never_counted)(jnp.ones((3,))).block_until_ready()
    finally:
        _reg.set_enabled(True)
    assert _of(SECONDS, "never_counted") == {}
    assert _of(PROGRAMS, "never_counted") == {}


_CACHE_SCRIPT = textwrap.dedent("""
    import json, sys
    import jax, jax.numpy as jnp
    if sys.argv[1]:
        jax.config.update("jax_compilation_cache_dir", sys.argv[1])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from horovod_tpu.observability import registry
    registry.watch_compiles()

    def cached_program(x):
        return jnp.cos(x) * 3

    def outcome():
        values = registry.snapshot()["hvdtpu_compile_programs_total"][
            "values"]
        return {k.split(",")[0]: v for k, v in values.items()
                if k.endswith(',program="cached_program"')}

    seen = []
    x = jnp.ones((4,))
    jax.jit(cached_program)(x).block_until_ready()
    seen.append(outcome())
    jax.clear_caches()
    jax.jit(cached_program)(x).block_until_ready()
    seen.append(outcome())
    print(json.dumps(seen))
""")


def _fresh_process(cache_dir):
    """The two compiles of one program in a fresh process: the first,
    then one after ``jax.clear_caches()``; the persistent cache in
    ``cache_dir`` or none."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_COMPILATION_CACHE",
                                "JAX_PERSISTENT_CACHE"))}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT), env.get("PYTHONPATH", "")]))
    done = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT, cache_dir],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.splitlines()[-1])


def test_the_persistent_cache_reads_miss_then_hit(tmp_path):
    fresh, reloaded = _fresh_process(str(tmp_path / "cache"))
    assert fresh == {'cache="miss"': 1.0}
    assert reloaded == {'cache="miss"': 1.0, 'cache="hit"': 1.0}


def test_without_the_persistent_cache_a_compile_reads_off():
    first, again = _fresh_process("")
    assert first == {'cache="off"': 1.0}
    assert again == {'cache="off"': 2.0}


def _tiny_cfg():
    return tfm.TransformerConfig(
        vocab=64, d_model=16, n_heads=2, n_layers=2, d_ff=32, max_seq=8,
        dtype=jnp.float32, use_flash=False, remat=False)


def test_make_of_the_train_step_adds_to_its_span():
    cfg = _tiny_cfg()
    opt = optax.adamw(1e-3)
    make, _, _ = build_train_step(cfg, hvd.mesh(), opt)
    params = jax.eval_shape(lambda: tfm.init_params(
        cfg, jax.random.PRNGKey(0)))
    before = _values(SETUP).get('phase="make"', 0.0)
    make(params, jax.eval_shape(opt.init, params))
    assert _values(SETUP)['phase="make"'] > before


def test_make_of_the_pipeline_step_adds_to_its_span():
    cfg = _tiny_cfg()
    opt = optax.sgd(0.1)
    mesh = create_mesh(devices=jax.devices()[:2], pp=2)
    make, _, _ = build_pipeline_train_step(cfg, mesh, opt)
    pparams = to_pipeline_params(
        cfg, tfm.init_params(cfg, jax.random.PRNGKey(0)), 2, 1)
    before = _values(SETUP).get('phase="make"', 0.0)
    make(pparams, jax.eval_shape(opt.init, pparams))
    assert _values(SETUP)['phase="make"'] > before


def test_init_adds_to_its_span():
    before = _values(SETUP).get('phase="init"', 0.0)
    hvd.init()
    assert _values(SETUP)['phase="init"'] > before


@pytest.mark.parametrize("fun_name, program", [
    ("hvd_train_step", "hvd_train_step"),
    ("jit(hvd_train_step)", "hvd_train_step"),
    ("jit_hvd_train_step", "hvd_train_step"),
    ("pmap(f)", "pmap(f)"),
])
def test_one_program_name_across_the_phases(fun_name, program):
    assert _reg.program_name(fun_name) == program
