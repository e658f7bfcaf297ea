"""Traffic kind ``train``: a training loop over the program's in-jit
step (``build_train_step``) on a GPT-2-shaped dense configuration, fed
by the program's loader and prefetcher. The loop is the ONE training
loop of the benchmark: a kind of another model (``train_nemotron_h``)
hands ``run_model`` its configuration object and what differs between
models (``_run``'s ``model``) and owns no set-up, warm-up or window.

A kind is found by the ``kind`` field of a traffic file, as
``benchmark/kinds/<kind>.py``, and is one function ``run(ctx)``. It owns
its set-up, its loops, its checks and its end-to-end values;
``harness.py`` owns what every kind shares (the chip checks, the
profiler, the per-layer readers, the result line) and documents ``ctx``
and the dict a kind returns.

Set-up (all of it ``setup_s``): ``hvd.init`` on the cell's chips and the
mesh of the traffic file's ``layout``; tokens from the seed through the
loader and prefetcher; weights on the device in one jitted call from the
seed; the plain float32 reference's loss on the first batch; the one
step program lowered and compiled (or loaded from the compile cache);
the first step, whose loss is held to the reference; warm-up steps until
the step time has settled.

Window, ``--trace 0``: a training loop as users write it. Steps are
dispatched ahead, each with the next batch of the loader; every ``n``
steps the host reads the loss, which ends a segment (``segments.py``).
``train_tok_s_per_chip`` is ALL the window's tokens over ALL its seconds
and chips; every segment's rate and their median go on an earlier line.
``--trace 1``: steps synced one by one, the profiler on for the first
few, then synced steps with it off for the step-time percentiles, until
``--seconds`` have passed AND ten samples are in."""

import math
import statistics
import time
from types import SimpleNamespace

import numpy as np

from benchmark import flops, reference, segments
from benchmark import tokens as token_gen
from benchmark.harness import Refused, say

WARMUP_MIN_STEPS = 5
WARMUP_MAX_STEPS = 15
WARMUP_SETTLED = 0.005      # last three step times within 0.5%
TRACE_MIN_STEPS, TRACE_MAX_STEPS, TRACE_SECONDS = 3, 8, 2.0
# the step-time percentiles need a floor of samples, not of seconds: a
# traced run of a slow step goes on past ``--seconds`` to reach it
SYNCED_MIN_SAMPLES = 10
# Mesh axes build_train_step binds through the TransformerConfig; 'dp'
# alone is Horovod's world mesh.
LAYOUT_AXES = ("dp", "tp", "sp")


def transformer_config(config, traffic):
    """The program's TransformerConfig of a GPT-2-shaped configuration
    file under a traffic file's numerics and layout."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm
    if config.get("family") != "gpt2-dense":
        raise Refused(f"configuration family {config.get('family')!r}: "
                      "kind 'train' runs 'gpt2-dense' files")
    if traffic["seq"] > config["n_positions"]:
        raise Refused(f"traffic seq {traffic['seq']} exceeds the "
                      f"configuration's n_positions "
                      f"{config['n_positions']}")
    policy = traffic["remat_policy"]
    layout = traffic["layout"]
    return tfm.TransformerConfig(
        vocab=config["vocab_size"], d_model=config["n_embd"],
        n_layers=config["n_layer"], n_heads=config["n_head"],
        d_ff=config["n_inner"], max_seq=config["n_positions"],
        dtype=getattr(jnp, traffic["dtype"]), remat=policy != "none",
        remat_policy=policy if policy != "none" else "full",
        use_flash=traffic["use_flash"],
        logits_bf16=traffic["logits_bf16"],
        loss_chunk=traffic["loss_chunk"],
        tp_axis="tp" if "tp" in layout else None,
        sp_axis="sp" if "sp" in layout else None)


def optimizer(spec):
    import jax.numpy as jnp
    import optax
    if spec["name"] != "adamw":
        raise Refused(f"optimizer {spec['name']!r}: only 'adamw' so far")
    mu = spec.get("mu_dtype")
    return optax.adamw(spec["learning_rate"],
                       mu_dtype=getattr(jnp, mu) if mu else None)


def build_mesh(layout, devices):
    """The mesh of a traffic file's ``layout`` (axis name -> size) over
    the cell's chips. ``{"dp": n}`` is ``hvd.mesh()``, the world mesh a
    Horovod user gets; further axes go to the program's
    ``create_mesh``. Only an axis ``build_train_step`` cannot bind, or
    sizes that do not multiply to the chips, are refused."""
    import horovod_tpu as hvd
    from horovod_tpu.parallel.mesh import create_mesh
    unknown = sorted(set(layout) - set(LAYOUT_AXES))
    if unknown:
        raise Refused(f"layout axes {unknown}: kind 'train' binds "
                      f"{list(LAYOUT_AXES)}")
    if math.prod(layout.values()) != len(devices):
        raise Refused(f"layout {layout} does not multiply to the cell's "
                      f"{len(devices)} chip(s)")
    if set(layout) == {"dp"}:
        return hvd.mesh()
    return create_mesh(devices=devices, **layout)


def _spec_axes(spec):
    axes = set()
    for part in spec:
        if part is not None:
            axes.update(part if isinstance(part, tuple) else (part,))
    return axes


def replicas_equal(params, specs, mesh):
    """Whether every copy of every parameter holds the same bits: one
    uint32 checksum per leaf and device (bit patterns summed, so any
    differing bit shows), constant along each mesh axis that the leaf's
    spec does not shard."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    names = mesh.axis_names

    def per_device(tree):
        return [jnp.sum(lax.bitcast_convert_type(x, jnp.uint32),
                        dtype=jnp.uint32).reshape((1,) * len(names))
                for x in jax.tree_util.tree_leaves(tree)]

    sums = jax.jit(jax.shard_map(
        per_device, mesh=mesh, in_specs=(specs,), out_specs=P(*names),
        check_vma=False))(params)
    leaf_specs = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    for checksum, spec in zip(sums, leaf_specs):
        checksum = np.asarray(checksum)         # mesh-shaped
        for k, axis in enumerate(names):
            if axis not in _spec_axes(spec) and not (
                    checksum == checksum.take([0], axis=k)).all():
                return False
    return True


def dense_model(cfg, config):
    """What ``_run`` asks of a model, for the GPT-2-shaped dense one:
    the program's ``transformer`` module, ``reference.py`` and
    ``flops.py``."""
    from horovod_tpu.models import transformer as tfm

    def against_reference(params, tok, tgt, tokens_per_step):
        t0 = time.perf_counter()
        ref_loss = reference.reference_loss(
            params, np.asarray(tok), np.asarray(tgt), config)
        return {"reference_loss": ref_loss,
                "loss_tolerance": reference.loss_tolerance(tokens_per_step),
                "spans": {"reference_s": time.perf_counter() - t0}}

    return SimpleNamespace(
        init_params=lambda key: tfm.init_params(cfg, key),
        param_specs=lambda: tfm.param_specs(cfg),
        against_reference=against_reference,
        flops_per_step=lambda batch, seq: flops.model_flops_per_step(
            config, batch, seq))


def run(ctx):
    config, traffic = ctx.cell["config"], ctx.cell["traffic"]
    cfg = transformer_config(config, traffic)
    return run_model(ctx, cfg, dense_model(cfg, config))


def run_model(ctx, cfg, model):
    """One run of a cell that trains ``cfg`` (a configuration object
    ``build_train_step`` takes) through the program's loader, prefetcher
    and in-jit step; ``model`` is what differs between models (``_run``).
    A kind of another model is its ``model_config`` and its ``model``,
    handed over here."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import data as hvd_data
    from horovod_tpu.parallel.train import build_train_step

    traffic = ctx.cell["traffic"]
    opt = optimizer(traffic["optimizer"])
    hvd.init(devices=ctx.devices)
    feed = None
    try:
        mesh = build_mesh(traffic["layout"], ctx.devices)
        chips = mesh.devices.size
        batch = traffic["batch_per_chip"] * chips

        # Input layer under test: the benchmark's tokens through the
        # program's sharded loader and device prefetcher.
        toks, tgts = token_gen.make_tokens(ctx.seed, traffic["sequences"],
                                           traffic["seq"], cfg.vocab)
        loader = hvd_data.build_loader(
            hvd_data.ArraySource(toks, tgts), batch_size=batch, rank=0,
            world_size=1, seed=int(ctx.seed) % (2 ** 31))
        make, shard_params, shard_batch = build_train_step(cfg, mesh, opt)
        feed = hvd_data.prefetch_to_device(
            loader, NamedSharding(mesh, P("dp" if "dp" in mesh.axis_names
                                          else None,
                                          getattr(cfg, "sp_axis", None))),
            depth=traffic["prefetch_depth"])

        def next_batch():
            b = next(feed)
            return shard_batch(b.data[0]), shard_batch(b.data[1])

        return _run(ctx, mesh, model, opt, make, shard_params, next_batch,
                    batch * traffic["seq"])
    finally:
        if feed is not None:
            feed.close()
            thread = getattr(feed, "_thread", None)
            if thread is not None:
                thread.join(timeout=10)
        hvd.shutdown()


def _run(ctx, mesh, model, opt, make, shard_params, next_batch,
         tokens_per_step):
    """Set-up after the mesh and the input feed exist, the window and
    the checks. ``model`` holds what differs between models:

    - ``init_params(key)``: the parameters, made inside one jitted call;
    - ``param_specs()``: their ``PartitionSpec``s, for the replica check;
    - ``against_reference(params, tok, tgt, tokens_per_step)``: the
      plain reference on the first batch, before the first step donates
      the weights. It returns ``reference_loss`` and ``loss_tolerance``
      (the first step's loss is held to them here) and may return
      ``spans`` (seconds by name, into the run's spans), further
      ``checks`` (name -> bool) with the numbers behind them as
      ``compared`` (name -> ``(number, limit)``), ``record`` (into the
      run's file) and ``layer_run`` (what else its readers read);
    - ``flops_per_step(batch, seq)``: the FLOPs ``mfu`` is taken over."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    traffic, watch = ctx.cell["traffic"], ctx.watch
    chips = mesh.devices.size
    spans = {}

    def on_mesh(specs):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))

    # Weights: one jitted call from the seed, on the device, in the
    # type they are trained in. Nothing is closed over as a constant.
    t0 = time.perf_counter()
    # The jitted function is a lambda of this call alone: ``model``
    # lives as long as the run, and a program jitted from a function it
    # holds would stay loaded on the device with it (15-19 MB of
    # ``peak_bytes_in_use`` in every cell, my chip runs, PR 35).
    params = jax.jit(lambda key: model.init_params(key),
                     out_shardings=NamedSharding(mesh, P()))(
                         jax.random.PRNGKey(int(ctx.seed)))
    params = shard_params(params)
    jax.block_until_ready(params)
    spans["weights_s"] = time.perf_counter() - t0

    # The plain reference on the first batch, before the first step
    # donates the weights.
    tok, tgt = next_batch()
    found = model.against_reference(params, tok, tgt, tokens_per_step)
    spans.update(found.get("spans", {}))
    ref_loss, tol = found["reference_loss"], found["loss_tolerance"]

    # make() says how the step wants the optimizer state laid out; it
    # is then made in that layout, in one jitted call.
    step, opt_specs = make(params, jax.eval_shape(opt.init, params))
    opt_state = jax.jit(opt.init, out_shardings=on_mesh(opt_specs))(params)
    hits, misses = watch.cache_hits, watch.cache_misses
    t0 = time.perf_counter()
    lowered = step.lower(params, opt_state, tok, tgt)
    spans["lower_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    spans["compile_s"] = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    say(f"lowered in {spans['lower_s']:.2f} s, compiled in "
        f"{spans['compile_s']:.2f} s (compile cache: "
        f"{watch.cache_hits - hits} hit, {watch.cache_misses - misses} "
        f"written)")
    if mem is not None:
        say("memory_analysis per device: argument "
            f"{mem.argument_size_in_bytes / 1e9:.3f} GB, output "
            f"{mem.output_size_in_bytes / 1e9:.3f} GB, alias "
            f"{mem.alias_size_in_bytes / 1e9:.3f} GB, temp "
            f"{mem.temp_size_in_bytes / 1e9:.3f} GB")

    # First step (held to the reference), then warm-up until settled.
    warm = []
    losses = []
    while True:
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, tok, tgt)
        losses.append(float(loss))
        warm.append(time.perf_counter() - t0)
        last = warm[-3:]
        settled = (len(warm) >= WARMUP_MIN_STEPS and
                   (max(last) - min(last)) <= WARMUP_SETTLED
                   * statistics.median(last))
        if settled or len(warm) >= WARMUP_MAX_STEPS:
            break
        tok, tgt = next_batch()
    first_loss = losses[0]
    step_s = statistics.median(warm[-3:])
    say(f"warm-up: {len(warm)} steps, "
        f"{' '.join(f'{1e3 * w:.1f}' for w in warm)} ms; step "
        f"{1e3 * step_s:.2f} ms")
    rel = abs(first_loss - ref_loss) / abs(ref_loss)
    say(f"first loss {first_loss:.5f}, reference {ref_loss:.5f}: relative "
        f"difference {rel:.2e} (tolerance {tol:.2e})")

    requests_before = watch.requests
    setup_s = time.perf_counter() - ctx.t_start
    if ctx.trace:
        window = _traced_window(ctx, step_s, compiled, params, opt_state,
                                next_batch)
    else:
        window = _window(ctx.seconds, step_s, compiled, params, opt_state,
                         next_batch)
    params = window.pop("params")
    window_losses = window.pop("losses")

    checks = {
        "first_loss_matches_reference": rel <= tol,
        **found.get("checks", {}),
        "losses_finite": all(math.isfinite(l)
                             for l in losses + window_losses),
        "loss_fell": window_losses[-1] < first_loss,
        "no_compile_in_window": watch.requests == requests_before,
    }
    if chips > 1:
        checks["replicas_equal"] = replicas_equal(
            params, model.param_specs(), mesh)

    out = {"setup_s": setup_s, "checks": checks,
           "compared": {"loss_rel": (rel, tol), **found.get("compared", {})},
           "attempted": window["steps"],
           "failed": sum(1 for l in window_losses
                         if not math.isfinite(l)),
           "record": {"spans": spans, "warmup_step_s": warm,
                      "first_loss": first_loss, "reference_loss": ref_loss,
                      "tokens_per_step": tokens_per_step,
                      "window_losses": window_losses,
                      **found.get("record", {})}}
    if ctx.trace:
        say(f"synced steps outside the profiler: "
            f"{len(window['step_seconds'])} samples")
        out["record"]["step_seconds"] = window["step_seconds"]
        out["layer_run"] = {
            "spans": dict(spans, input_wait_s=window["input_wait_s"]),
            "step_seconds": window["step_seconds"],
            "tokens_per_step": tokens_per_step,
            "model_flops_per_step": model.flops_per_step(
                tokens_per_step // traffic["seq"], traffic["seq"]),
            **found.get("layer_run", {}),
        }
    else:
        out["values"] = _end_to_end(window, setup_s, tokens_per_step,
                                    chips, out["record"])
    return out


def _end_to_end(window, setup_s, tokens_per_step, chips, record):
    """The untraced run's values; every segment's reading goes on an
    earlier line and into the run's file."""
    seconds = window["segment_seconds"]
    tokens = [window["steps_per_segment"] * tokens_per_step] * len(seconds)
    rates = segments.rates(tokens, seconds, chips)
    total = segments.total_rate(tokens, seconds, chips)
    median = statistics.median(rates)
    say(f"segments: {len(rates)} x {window['steps_per_segment']} steps; "
        f"seconds {' '.join(f'{s:.4f}' for s in seconds)}; tokens/s/chip "
        f"{' '.join(f'{r:.2f}' for r in rates)}; median segment "
        f"{median:.2f}; total tokens over total seconds {total:.2f}; "
        f"input wait "
        f"{1e3 * window['input_wait_s'] / window['steps']:.3f} ms/step")
    record.update(segment_seconds=seconds, segment_rates=rates,
                  segment_median_rate=median, total_rate=total,
                  steps_per_segment=window["steps_per_segment"])
    return {"setup_s": setup_s, "train_tok_s_per_chip": total}


def _window(seconds, step_s, compiled, params, opt_state, next_batch):
    """The untraced window: dispatch ahead, read the loss every ``n``
    steps. Nothing but the loop is inside the clock."""
    k, n = segments.plan(seconds, step_s)
    seg_seconds, seg_losses = [], []
    input_wait = 0.0
    t_prev = time.perf_counter()
    for _ in range(k):
        for _ in range(n):
            t0 = time.perf_counter()
            tok, tgt = next_batch()
            input_wait += time.perf_counter() - t0
            params, opt_state, loss = compiled(params, opt_state, tok, tgt)
        seg_losses.append(float(loss))
        now = time.perf_counter()
        seg_seconds.append(now - t_prev)
        t_prev = now
    return {"params": params, "steps": k * n, "steps_per_segment": n,
            "segment_seconds": seg_seconds, "losses": seg_losses,
            "input_wait_s": input_wait}


def _traced_window(ctx, step_s, compiled, params, opt_state, next_batch):
    """The traced window: steps synced one by one; the profiler on for
    the first few, host spans beside it; then synced steps with the
    profiler off for the step-time percentiles."""
    import jax
    from jax.profiler import TraceAnnotation

    traced_steps = max(TRACE_MIN_STEPS,
                       min(TRACE_MAX_STEPS,
                           math.ceil(TRACE_SECONDS / step_s)))
    losses = []
    t_begin = time.perf_counter()
    with ctx.profile():
        # One step more than counted: the traced window runs from the
        # first step's start to the last step's start.
        for _ in range(traced_steps + 1):
            with TraceAnnotation("bench/input"):
                tok, tgt = next_batch()
            with TraceAnnotation("bench/dispatch"):
                params, opt_state, loss = compiled(params, opt_state,
                                                   tok, tgt)
            with TraceAnnotation("bench/sync"):
                jax.block_until_ready(loss)
            with TraceAnnotation("bench/read_loss"):
                losses.append(float(loss))

    step_seconds = []
    input_wait = 0.0
    while (time.perf_counter() - t_begin < ctx.seconds
           or len(step_seconds) < SYNCED_MIN_SAMPLES):
        t0 = time.perf_counter()
        tok, tgt = next_batch()
        t1 = time.perf_counter()
        input_wait += t1 - t0
        params, opt_state, loss = compiled(params, opt_state, tok, tgt)
        jax.block_until_ready(loss)
        step_seconds.append(time.perf_counter() - t1)
        losses.append(float(loss))
    return {"params": params, "steps": len(losses), "losses": losses,
            "step_seconds": step_seconds, "input_wait_s": input_wait}
