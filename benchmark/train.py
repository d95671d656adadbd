"""Training cells (``kind: train``): the step exactly as ``fit()`` builds it
(``sharded_train_state`` + ``make_train_step``, state donated, flash
attention, the fused cross-entropy over ``return_hidden``, the optimizer from
``default_optimizer`` with the traffic file's ``TrainLoopConfig`` knobs), fed
seeded random tokens from the host, one loss readback every
``readback_every`` steps.

``correct`` does not hang on how far the loss fell inside the window: on
random tokens that is a property of the learning rate and differs from seed
to seed. After the window the initial state is made again from the seed and
the step is replayed on ONE batch: the program's loss must match the float32
reference before and after, and the reference's own loss on that batch must
have gone down.
"""

from __future__ import annotations

import gc
import math
import statistics
import time

from benchmark import reference, traffic


def run(cell: dict, ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from learning_jax_sharding_tpu.models.transformer import (
        Transformer,
        fused_next_token_loss,
    )
    from learning_jax_sharding_tpu.ops.flash_attention import make_flash_attn_fn
    from learning_jax_sharding_tpu.parallel import (
        build_mesh,
        mesh_sharding,
        put,
        single_device_mesh,
    )
    from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
    from learning_jax_sharding_tpu.training.loop import (
        TrainLoopConfig,
        default_optimizer,
    )
    from learning_jax_sharding_tpu.training.pipeline import (
        make_train_step,
        sharded_train_state,
    )

    spec, model = ctx.traffic, ctx.model
    dims = ctx.family.model_dims(model)
    batch, seq, every = spec["batch"], spec["seq"], spec["readback_every"]
    # ``mesh`` in the traffic file (``{"shape": [2, 2], "axes": ["data",
    # "model"]}``) spreads the step over the cell's chips; without it, one.
    if "mesh" in spec:
        mesh = build_mesh(tuple(spec["mesh"]["shape"]), tuple(spec["mesh"]["axes"]))
        flash = make_flash_attn_fn(mesh, RULES_DP_TP, interpret=not ctx.on_tpu)
    else:
        mesh = single_device_mesh()
        flash = make_flash_attn_fn(interpret=not ctx.on_tpu)
    cfg = ctx.family.to_config(
        model, dtype=jnp.bfloat16, param_dtype=jnp.float32, attn_fn=flash,
        **spec.get("model_overrides", {}),
    )
    sh = mesh_sharding(mesh, "data", None)
    host = traffic.token_batches(batch, seq, dims["vocab_size"], ctx.seed, spec["batch_pool"])

    def on_device(i):
        tokens = host[i % len(host)]
        return {"inputs": put(tokens[:, :-1], sh), "targets": put(tokens[:, 1:], sh)}

    optimizer = default_optimizer(
        TrainLoopConfig(global_batch_size=batch, **spec["optimizer"])
    )
    first = on_device(0)

    module = Transformer(cfg)      # one object: it is part of the state's tree type

    def make_state():
        return sharded_train_state(
            module, optimizer, first["inputs"], {"params": ctx.key},
            mesh, RULES_DP_TP,
        )

    state, state_sh = make_state()
    jax.block_until_ready(state)
    ctx.phase("state_on_device")
    step = make_train_step(
        state_sh, {k: v.sharding for k, v in first.items()}, mesh, RULES_DP_TP,
        loss_fn=fused_next_token_loss, loss_needs_params=True,
        apply_kwargs={"return_hidden": True},
    )
    # Step 0 compiles; its loss is the one the reference is held against.
    state, loss = step(state, first)
    losses = [float(loss)]
    state, loss = step(state, on_device(1))
    losses.append(float(loss))
    ctx.phase("step_compiled_and_warm")

    seconds = ctx.seconds
    n = 2
    t_start = time.perf_counter()
    ctx.window_opens(t_start)
    # One group of ``every`` steps is always in flight while the host reads
    # the loss of the group before it, so a pause of the host shorter than a
    # group never idles the chip (a run of the first version lost 1.1 s so).
    # The window closes once the group in flight will carry it past
    # ``seconds``; the clock stops when that group's loss is on the host.
    steps, groups_read, pending = 0, 0, None
    while True:
        for _ in range(every):
            state, loss = step(state, on_device(n))
            n += 1
        steps += every
        if pending is not None:
            losses.append(float(pending))       # host readback: that group is done
            groups_read += 1
            elapsed = time.perf_counter() - t_start
            if elapsed + elapsed / groups_read >= seconds:
                break
        pending = loss
    losses.append(float(loss))
    t_stop = time.perf_counter()
    window_s = t_stop - t_start
    window_compiles = ctx.window_closes()

    traced_steps = 0
    if ctx.trace:
        ctx.start_trace()
        for _ in range(spec["trace_steps"]):
            state, loss = step(state, on_device(n))
            n += 1
        jax.block_until_ready(loss)
        ctx.stop_trace()
        traced_steps = spec["trace_steps"]
        losses.append(float(loss))

    tokens = batch * seq
    e2e = {"train_tok_s": steps * tokens / window_s}
    peak = ctx.peak_bytes()
    non_finite = sum(not math.isfinite(x) for x in losses)

    # After the window the initial state is made again from the seed (the
    # first step donated it) and the step is replayed ``replay_steps`` times
    # on the first batch alone, with the reference's loss on that batch
    # taken before and after. No batch noise and a small rate (the replay
    # starts at the schedule's step 0), so a correct gradient and update
    # must lower it; the program's own loss is held against the reference at
    # both ends.
    del state
    gc.collect()
    check = model["check"]
    tol, k = float(check["loss_tol"]), int(check["replay_steps"])
    ref = ctx.family.reference_fn(dims)
    state, _ = make_state()
    ref_before = reference.reference_loss(ref, state.params, host[0])
    replay = []
    for _ in range(k):
        state, loss = step(state, first)
        replay.append(loss)
    ref_after = reference.reference_loss(ref, state.params, host[0])
    state, loss = step(state, first)          # the program's loss at those parameters
    replay = [float(x) for x in (*replay, loss)]
    del state, step
    diff = abs(losses[0] - ref_before)
    diff_after = abs(replay[-1] - ref_after)
    descent = ref_before - ref_after
    non_finite += sum(not math.isfinite(x) for x in replay)
    # Said, not judged: whether the loss fell inside the window.
    fell = statistics.median(losses[-3:]) < losses[0]
    ctx.info(
        "losses", step0=losses[0], reference_f32=ref_before, diff=diff,
        replay_steps=k, replay_step0=replay[0], replay_last=replay[-1],
        reference_f32_after=ref_after, diff_after=diff_after, tolerance=tol,
        descent=descent, descent_min=float(check["descent_min"]),
        read=losses[1:], non_finite=non_finite, fell_in_window=fell,
        steps_in_window=steps, window_s=window_s,
        step_ms=window_s / steps * 1e3, traced_steps=traced_steps,
    )
    return {
        "e2e": e2e,
        "observed": {
            "work": {
                "model": dims, "batch": batch, "seq": seq,
                "steps_in_slice": traced_steps,
            },
        },
        "attempted": n, "failed": non_finite, "peak_bytes": peak,
        "correct": bool(
            diff <= tol and diff_after <= tol
            and descent >= float(check["descent_min"])
            and non_finite == 0 and window_compiles == 0
        ),
    }
