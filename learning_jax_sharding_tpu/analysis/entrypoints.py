"""The jitted entry points shardcheck holds under contract.

One place that knows how to BUILD each hot program the repo ships —
train step, ZeRO-1 update, serving prefill/decode, MoE all-to-all
dispatch, ring/Ulysses attention — small enough to compile on the
8-device emulated mesh in seconds, shaped exactly like the production
path (same builders: ``make_train_step``, ``ContinuousEngine``,
``moe_a2a_ff``, ``ops.ring_attention``/``ulysses``), so the golden
contracts in ``analysis/golden/`` pin the real partitioning decisions.

Every entry point resolves to one or more :class:`EntryProgram` records
(name, mesh, optimized-HLO supplier, optional donation-audit hook).
``scripts/shardcheck.py --update-golden`` regenerates the goldens from
these; the checking path compiles the same programs and diffs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

from learning_jax_sharding_tpu.parallel.hlo import compiled_hlo


@dataclasses.dataclass
class EntryProgram:
    """One contract-checkable compiled program.

    ``hlo`` is a thunk (compiles are paid lazily, once); ``donation``
    optionally audits the program's buffer donations
    (``analysis.donation.donation_report``-shaped dict); ``jaxpr``
    optionally lints the program's trace
    (``analysis.jaxpr_lint.lint_jaxpr`` findings, where-prefixed with
    the entry-point name so per-program budgets can key on it);
    ``shardflow`` runs the pre-compile GSPMD propagation simulator over
    the same program (``analysis.shardflow.trace_shardflow`` — trace
    only, no compile) and returns its
    :class:`~learning_jax_sharding_tpu.analysis.shardflow.
    ShardflowReport`, which the ``--explain`` pass reconciles against
    this entry point's golden contract.
    """

    name: str
    mesh: Any
    hlo: Callable[[], str]
    donation: Callable[[], dict] | None = None
    jaxpr: Callable[[], list] | None = None
    shardflow: Callable[[], Any] | None = None


def _mesh24():
    from learning_jax_sharding_tpu.parallel import build_mesh

    return build_mesh((2, 4), ("data", "model"))


def _tiny_cfg():
    import dataclasses as dc

    import jax.numpy as jnp

    from learning_jax_sharding_tpu.models.transformer import CONFIG_TINY

    return dc.replace(CONFIG_TINY, dtype=jnp.float32)


def _train_state_and_step(
    mesh, *, zero1_axis=None, with_grad_norm=False, skip_nonfinite=False
):
    import jax

    from learning_jax_sharding_tpu.data.datasets import SyntheticLMDataset
    from learning_jax_sharding_tpu.data.loader import ShardedBatchLoader
    from learning_jax_sharding_tpu.models.transformer import (
        Transformer,
        next_token_loss,
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

    cfg = _tiny_cfg()
    dataset = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=32, seed=0)
    loader = ShardedBatchLoader(dataset, mesh, 8, spec=("data",))
    batch = loader.batch_at(0)
    opt = default_optimizer(TrainLoopConfig(steps=4, global_batch_size=8))
    state, state_sh = sharded_train_state(
        Transformer(cfg), opt, batch["inputs"],
        {"params": jax.random.key(0)}, mesh, RULES_DP_TP,
        zero1_axis=zero1_axis,
    )
    step = make_train_step(
        state_sh, {k: v.sharding for k, v in batch.items()}, mesh,
        RULES_DP_TP, loss_fn=next_token_loss,
        with_grad_norm=with_grad_norm, skip_nonfinite=skip_nonfinite,
    )
    return cfg, state, batch, step, RULES_DP_TP


def _train_like(
    name: str, *, zero1_axis=None, with_grad_norm=False,
    skip_nonfinite=False, audit=True
) -> EntryProgram:
    import dataclasses as dc

    from learning_jax_sharding_tpu.analysis.donation import (
        check_train_step_donation,
    )
    from learning_jax_sharding_tpu.parallel.logical import activate

    mesh = _mesh24()
    built: dict = {}

    def ensure():
        if not built:
            built["v"] = _train_state_and_step(
                mesh, zero1_axis=zero1_axis, with_grad_norm=with_grad_norm,
                skip_nonfinite=skip_nonfinite,
            )
        return built["v"]

    def ensure_compiled():
        # ONE AOT lower+compile serves the contract pass (HLO text) AND
        # the donation pass (alias header + args_info) — the single
        # largest line of the CI budget, paid once per entry point.
        if "text" not in built:
            cfg, state, batch, step, rules = ensure()
            with activate(mesh, rules):
                built["lowered"] = step.jitted.lower(state, batch)
                built["text"] = built["lowered"].compile().as_text()
        return built["lowered"], built["text"]

    def hlo():
        return ensure_compiled()[1]

    def donation():
        cfg, state, batch, step, rules = ensure()
        lowered, text = ensure_compiled()
        with activate(mesh, rules):
            return check_train_step_donation(
                step, state, batch, cfg=cfg, precompiled=(lowered, text),
            )

    def jaxpr():
        from learning_jax_sharding_tpu.analysis.jaxpr_lint import lint_jaxpr

        cfg, state, batch, step, rules = ensure()
        with activate(mesh, rules):
            findings = lint_jaxpr(step.jitted, state, batch)
        # Prefix with the entry-point name so baseline.json's per-program
        # jaxpr budgets (and the reader) know which trace this is.
        return [
            dc.replace(f, where=f"{name}:{f.where}") for f in findings
        ]

    def shardflow():
        from learning_jax_sharding_tpu.analysis.shardflow import (
            trace_shardflow,
        )

        cfg, state, batch, step, rules = ensure()
        with activate(mesh, rules):
            return trace_shardflow(name, step.jitted, state, batch, mesh=mesh)

    if not audit:
        # Contract-golden-only variants (e.g. train_step_gn): skip the
        # donation/jaxpr hooks so the jaxpr pass doesn't pay a duplicate
        # compile for a program that differs only in its epilogue.
        return EntryProgram(name, mesh, hlo, shardflow=shardflow)
    return EntryProgram(name, mesh, hlo, donation, jaxpr, shardflow)


def _sharded_serving_params(model, mesh, rules):
    """Params BORN SHARDED under the serving rules (the sharded-init
    pipeline, same as a trained state would arrive) — relowering with
    replicated params would record a vacuous no-collectives contract."""
    import flax.linen as nn
    import jax

    from learning_jax_sharding_tpu.parallel.logical import (
        activate,
        tree_shardings,
    )

    probe = np.zeros((2, 8), np.int32)

    def init(r, t):
        return model.init({"params": r}, t)

    with activate(mesh, rules):
        abstract = jax.eval_shape(init, jax.random.key(0), probe)
        shardings = tree_shardings(abstract, mesh, rules)
        return jax.jit(
            lambda r, t: nn.meta.unbox(init(r, t)),
            out_shardings=shardings,
        )(jax.random.key(0), probe)["params"]


def _engine_audit(built: dict, ensure: Callable) -> Callable[[], dict]:
    """The donation hook of the entries one live engine contributes:
    ``ContinuousEngine.donation_audit`` under the contract names, made
    once (``ensure`` builds and serves the engine into ``built``)."""

    def audit():
        if "donation" not in built:
            ensure()
            eng = built["eng"]
            built["donation"] = {
                eng.contract_name(k): v
                for k, v in eng.donation_audit().items()
            }
        return built["donation"]

    return audit


def _engine_programs(
    *, speculative: bool, mixed: bool = False, adapters: bool = False,
    horizon: int = 1, compression: bool = False,
) -> list[EntryProgram]:
    """Prefill + decode via a real (tiny) ContinuousEngine: one short
    serve populates the dispatch-arg caches, then each program relowers
    AOT (``ContinuousEngine.program_hlo``) under the engine's own golden
    names (``contract_name`` — ``spec_``-prefixed for the speculative
    family, whose refill also prefills the draft cache). first_refill is
    covered too — single-chunk prefills must not be silently
    contract-free. With ``mixed`` the engine runs the FUSED
    refill+decode scheduler and contributes only its ``mixed_step`` /
    ``spec_mixed_step`` golden (the refill/decode family is already
    pinned by the split engines). With ``adapters`` (round 12) the
    mixed engine carries an :class:`~learning_jax_sharding_tpu.tenancy.
    AdapterPool` and the contract is ``adapter_mixed_step`` /
    ``spec_adapter_mixed_step`` — the per-row LoRA gather + batch-1
    merged apply must add NO collectives beyond the base mixed step
    (adapter slices are co-sharded with the kernels they adapt). With
    ``horizon > 1`` (round 16) the engine dispatches the SCANNED
    multi-step family instead and contributes the ``multi_step`` /
    ``spec_multi_step`` / ``adapter_multi_step`` /
    ``spec_adapter_multi_step`` golden — the contract that fusing N
    iterations into one ``lax.scan`` adds ZERO collectives over N× the
    single-step multiset (shardflow prices the scanned body at the
    horizon trip count). With ``compression`` (round 22) the engine
    carries ``comm_compression=CommCompression()`` and the contract is
    the ``_q8`` variant (``mixed_step_q8`` / ``multi_step_q8``): the
    golden pins the quantized TP matmul's collective shape — the FF
    block's fp all-gather replaced by int8-payload + fp32-scale
    all-gathers — so a regression that silently falls back to the
    uncompressed reduction (or adds an unpriced collective around the
    codec) fails the contract, not just the bench."""
    import dataclasses as dc

    from learning_jax_sharding_tpu.models.serving import ContinuousEngine
    from learning_jax_sharding_tpu.models.transformer import Transformer
    from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING

    mesh = _mesh24()
    built: dict = {}

    def ensure():
        if built:
            return built["hlo"]
        cfg = _tiny_cfg()
        params = _sharded_serving_params(
            Transformer(cfg), mesh, RULES_TP_SERVING
        )
        kwargs: dict = dict(mixed=mixed) if mixed else {}
        if horizon > 1:
            kwargs["horizon"] = horizon
        if compression:
            from learning_jax_sharding_tpu.parallel.compression import (
                CommCompression,
            )

            kwargs["comm_compression"] = CommCompression()
        d_params = None
        if speculative:
            d_cfg = dc.replace(cfg, num_layers=1)
            d_params = _sharded_serving_params(
                Transformer(d_cfg), mesh, RULES_TP_SERVING
            )
            kwargs.update(draft_config=d_cfg, num_draft=2)
        if adapters:
            import jax

            from learning_jax_sharding_tpu.tenancy import AdapterPool
            from learning_jax_sharding_tpu.training.lora import init_lora

            pool = AdapterPool(params, slots=2, rank=4, mesh=mesh)
            # B must be nonzero or the adapted row computes the base
            # function and XLA could fold the gather away.
            pool.add(
                "tenant", jax.tree.map(
                    lambda x: x + 0.01, init_lora(jax.random.key(1), params, 4)
                ),
            )
            kwargs["adapter_pool"] = pool
        eng = ContinuousEngine(
            cfg, mesh, RULES_TP_SERVING,
            batch_size=2, max_new_tokens=8, refill_chunk=16,
            decode_block_steps=4, **kwargs,
        )
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in (20, 5)
        ]
        if adapters:
            # serve() has no per-request adapter plumbing (adapters are a
            # continuous-engine tenancy feature): drive the arrival +
            # step loop directly, one base row and one adapted row.
            for p, name in zip(prompts, (None, "tenant")):
                eng.add_request(p, adapter=name)
            while eng.has_work():
                eng.step(params, d_params)
        else:
            eng.serve(params, prompts, draft_params=d_params)
        built["eng"] = eng
        built["hlo"] = {
            eng.contract_name(k): v for k, v in eng.program_hlo().items()
        }
        return built["hlo"]

    def explain():
        if "sf" not in built:
            ensure()
            built["sf"] = built["eng"].explain_collectives()
        return built["sf"]

    audit = _engine_audit(built, ensure)

    if compression:
        # The q8 engines contribute only their fused-family golden (the
        # engine names them itself: contract_name suffixes _q8 while the
        # compression is live).
        names = ("multi_step_q8",) if horizon > 1 else ("mixed_step_q8",)
    elif adapters and horizon > 1:
        names = (
            ("spec_adapter_multi_step",) if speculative
            else ("adapter_multi_step",)
        )
    elif horizon > 1:
        names = ("spec_multi_step",) if speculative else ("multi_step",)
    elif adapters:
        names = (
            ("spec_adapter_mixed_step",) if speculative
            else ("adapter_mixed_step",)
        )
    elif mixed:
        names = ("spec_mixed_step",) if speculative else ("mixed_step",)
    else:
        names = (
            ("spec_first_prefill", "spec_prefill", "spec_decode_step")
            if speculative else ("first_prefill", "prefill", "decode_step")
        )
    return [
        EntryProgram(
            name, mesh, lambda name=name: ensure()[name],
            donation=lambda name=name: audit()[name],
            shardflow=lambda name=name: explain()[name],
        )
        for name in names
    ]


def _serving_programs() -> list[EntryProgram]:
    return [
        *_engine_programs(speculative=False),
        *_engine_programs(speculative=True),
        *_engine_programs(speculative=False, mixed=True),
        *_engine_programs(speculative=True, mixed=True),
        *_engine_programs(speculative=False, mixed=True, adapters=True),
        *_engine_programs(speculative=True, mixed=True, adapters=True),
        # The device-resident multi-step family (round 16): one scanned
        # program per engaged family at horizon=4 — the golden pins that
        # fusing the horizon adds no collectives over N single steps.
        *_engine_programs(speculative=False, mixed=True, horizon=4),
        *_engine_programs(speculative=True, mixed=True, horizon=4),
        *_engine_programs(
            speculative=False, mixed=True, adapters=True, horizon=4
        ),
        *_engine_programs(
            speculative=True, mixed=True, adapters=True, horizon=4
        ),
        # The comm-compression regime (round 22): the fused families
        # recompiled with the quantized TP all-reduce — their own
        # goldens, because the int8-payload collectives are a DIFFERENT
        # multiset from the fp programs they stand in for.
        *_engine_programs(speculative=False, mixed=True, compression=True),
        *_engine_programs(
            speculative=False, mixed=True, horizon=4, compression=True
        ),
    ]


def _kv_transfer_programs() -> list[EntryProgram]:
    """The disaggregated-handoff device programs (round 11 —
    ``fleet/kv_transfer.py`` rides between them): ``kv_export`` slices
    one retired request's cache row, ``kv_ingest`` writes an externally
    produced row into a free slot. Their goldens pin the handoff's
    claim that the DEVICE side adds no surprise collectives — the
    cross-replica byte movement lives entirely in the explicit,
    counted host transfer plan. Built on a live tiny engine with
    born-sharded params (the real TP serving layout): one short serve
    retires a request, export + self-ingest populate the dispatch-arg
    caches, then each program relowers AOT under its contract name."""
    from learning_jax_sharding_tpu.models.serving import ContinuousEngine
    from learning_jax_sharding_tpu.models.transformer import Transformer
    from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING

    mesh = _mesh24()
    built: dict = {}

    def ensure():
        if built:
            return built["hlo"]
        cfg = _tiny_cfg()
        params = _sharded_serving_params(
            Transformer(cfg), mesh, RULES_TP_SERVING
        )
        eng = ContinuousEngine(
            cfg, mesh, RULES_TP_SERVING,
            batch_size=2, max_new_tokens=4, refill_chunk=16,
            decode_block_steps=4,
        )
        rng = np.random.default_rng(0)
        prompt = rng.integers(
            1, cfg.vocab_size, size=(9,)
        ).astype(np.int32)
        (out,) = eng.serve(params, [prompt])
        rows, _length = eng.export_kv(0)
        eng.ingest_kv(
            params, prompt, int(out[len(prompt)]), rows, rid=1,
        )
        built["eng"] = eng
        built["hlo"] = {
            eng.contract_name(k): v for k, v in eng.program_hlo().items()
        }
        return built["hlo"]

    def explain():
        if "sf" not in built:
            ensure()
            built["sf"] = built["eng"].explain_collectives()
        return built["sf"]

    audit = _engine_audit(built, ensure)

    return [
        EntryProgram(
            name, mesh, lambda name=name: ensure()[name],
            donation=lambda name=name: audit()[name],
            shardflow=lambda name=name: explain()[name],
        )
        for name in ("kv_export", "kv_ingest")
    ]


def _kv_page_programs(*, compression: bool = False) -> list[EntryProgram]:
    """The KV tier ladder's device programs (round 15 —
    ``fleet/kv_economy.py`` rides between them): ``kv_page_spill``
    gathers one physical page's K/V leaves for demotion to the host
    tier, ``kv_page_fill`` writes a promoted page back into a freshly
    allocated pool slot. Their goldens pin the tier ladder's claim that
    demotion/promotion is pure LOCAL page movement — every cross-tier
    byte travels in the counted ``HostBuffer`` transfer plans, and the
    device side adds ZERO collectives. Built like the handoff programs
    but on a PAGED prefix-cache engine (the only kind that tiers): one
    short serve retains a prefix chain, spill + fill of its deepest
    page populate the dispatch-arg caches, then each program relowers
    AOT under its contract name. With ``compression`` the engine
    carries the KV codec (``CommCompression(collectives=False)``) and
    the goldens are ``kv_page_spill_q8``/``kv_page_fill_q8`` —
    bit-identical DEVICE programs to the uncompressed pair (the codec
    runs in the host plan, after the gather / before the write), named
    apart because they pin the byte-movement regime the page rows were
    audited under."""
    from learning_jax_sharding_tpu.models.serving import ContinuousEngine
    from learning_jax_sharding_tpu.models.transformer import Transformer
    from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING

    mesh = _mesh24()
    built: dict = {}

    def ensure():
        if built:
            return built["hlo"]
        cfg = dataclasses.replace(_tiny_cfg(), decode_attention="blocked")
        params = _sharded_serving_params(
            Transformer(cfg), mesh, RULES_TP_SERVING
        )
        kwargs: dict = {}
        if compression:
            from learning_jax_sharding_tpu.parallel.compression import (
                CommCompression,
            )

            kwargs["comm_compression"] = CommCompression(collectives=False)
        eng = ContinuousEngine(
            cfg, mesh, RULES_TP_SERVING,
            batch_size=2, max_new_tokens=4, refill_chunk=16,
            paged_pages=10, page_size=4, prefix_cache=True, **kwargs,
        )
        rng = np.random.default_rng(0)
        prompt = rng.integers(
            1, cfg.vocab_size, size=(9,)
        ).astype(np.int32)
        eng.serve(params, [prompt])
        (key, *_) = eng.retained_prefixes()
        rows, _ = eng.spill_page(key, drop=True)
        eng.fill_page(key, rows)
        built["eng"] = eng
        built["hlo"] = {
            eng.contract_name(k): v for k, v in eng.program_hlo().items()
        }
        return built["hlo"]

    def explain():
        if "sf" not in built:
            ensure()
            built["sf"] = built["eng"].explain_collectives()
        return built["sf"]

    audit = _engine_audit(built, ensure)

    return [
        EntryProgram(
            name, mesh, lambda name=name: ensure()[name],
            donation=lambda name=name: audit()[name],
            shardflow=lambda name=name: explain()[name],
        )
        for name in (
            ("kv_page_spill_q8", "kv_page_fill_q8") if compression
            else ("kv_page_spill", "kv_page_fill")
        )
    ]


def _swap_reshard_programs() -> list[EntryProgram]:
    """The weight-hot-swap staging programs (round 12). When
    ``ContinuousEngine.swap_weights`` stages a checkpoint that arrives in
    a TRAINING layout into the engine's serving layout on the same
    device set, ``parallel.resharding.device_reshard`` compiles ONE
    jitted identity with ``out_shardings`` pinned. The source here is
    the FSDP layout (``RULES_FSDP``: EMBED over 'data', VOCAB whole) —
    the layout whose params tree actually DIFFERS from serving;
    ``RULES_DP_TP`` kernels already match the serving placement
    leaf-for-leaf, which would record a vacuous empty contract. The
    golden (``swap_reshard``) pins the claim the zero-downtime story
    rests on: the layout change is pure data movement — all-gathers
    over 'data', slices onto 'model' — with no arithmetic that could
    perturb the swapped weights. ``swap_reshard_quant`` is the same
    program over a ``quantize_tree``'d checkpoint (a quantized serving
    engine swaps {q:int8, scale:f32} leaves; the dtypes must survive
    the move — a dequant/requant sneaking in would silently change the
    model). Both lower the REAL ``device_reshard`` program via its
    ``jit_cache`` rather than a lookalike jit, so drift in the swap
    path itself trips the contract."""
    from learning_jax_sharding_tpu.parallel.logical import (
        RULES_FSDP,
        RULES_TP_SERVING,
    )
    from learning_jax_sharding_tpu.parallel.resharding import device_reshard

    mesh = _mesh24()

    def builders_for(quant: bool):
        built: dict = {}

        def ensure():
            if built:
                return built
            import jax

            from learning_jax_sharding_tpu.models.quantize import quantize_tree
            from learning_jax_sharding_tpu.models.transformer import Transformer

            cfg = _tiny_cfg()
            model = Transformer(cfg)
            src = _sharded_serving_params(model, mesh, RULES_FSDP)
            # Destination = the layout a serving engine's installed tree
            # actually carries (born-sharded under the serving rules; for
            # the quant variant, the shardings XLA propagates through
            # quantize_tree — exactly what the engine's cast cache holds).
            dst_tree = _sharded_serving_params(model, mesh, RULES_TP_SERVING)
            if quant:
                src = quantize_tree(src)
                dst_tree = quantize_tree(dst_tree)
            dst = jax.tree.map(lambda x: x.sharding, dst_tree)
            cache: dict = {}
            device_reshard(src, dst, jit_cache=cache)
            (fn,) = cache.values()
            built.update(src=src, dst=dst, fn=fn)
            return built

        def hlo():
            b = ensure()
            return b["fn"].lower(b["src"]).compile().as_text()

        def shardflow(name):
            from learning_jax_sharding_tpu.analysis.shardflow import (
                trace_shardflow,
            )

            b = ensure()
            return trace_shardflow(
                name, b["fn"], b["src"], mesh=mesh, out_shardings=b["dst"],
            )

        return hlo, shardflow

    out = []
    for name, quant in (
        ("swap_reshard", False), ("swap_reshard_quant", True)
    ):
        hlo, shardflow = builders_for(quant)
        out.append(EntryProgram(
            name, mesh, hlo,
            shardflow=lambda name=name, sf=shardflow: sf(name),
        ))
    return out


def _zero1_q8() -> EntryProgram:
    """The quantized-comm ZeRO-1 update (``training.zero.
    make_zero1_update(quantized_comm=True)``): its golden pins the int8
    ring sync — collective-permutes on the data axis inside the
    reduce-scatter/all-gather loops — next to the model-axis collectives
    the plain ``zero1_update`` already records."""
    import jax

    from learning_jax_sharding_tpu.parallel.logical import activate

    mesh = _mesh24()
    built: dict = {}

    def ensure():
        if built:
            return built
        from learning_jax_sharding_tpu.models.transformer import (
            next_token_loss,
        )
        from learning_jax_sharding_tpu.training.zero import (
            make_zero1_update,
        )

        cfg, state, batch, _, rules = _train_state_and_step(
            mesh, zero1_axis="data"
        )
        step = make_zero1_update(
            jax.tree.map(lambda x: x.sharding, state),
            {k: v.sharding for k, v in batch.items()}, mesh, rules,
            loss_fn=next_token_loss, quantized_comm=True,
        )
        built.update(state=state, batch=batch, step=step, rules=rules)
        return built

    def hlo():
        b = ensure()
        with activate(mesh, b["rules"]):
            return b["step"].jitted.lower(
                b["state"], b["batch"]
            ).compile().as_text()

    def shardflow():
        from learning_jax_sharding_tpu.analysis.shardflow import (
            trace_shardflow,
        )

        b = ensure()
        with activate(mesh, b["rules"]):
            return trace_shardflow(
                "zero1_update_q8", b["step"].jitted, b["state"], b["batch"],
                mesh=mesh,
            )

    return EntryProgram("zero1_update_q8", mesh, hlo, shardflow=shardflow)


def _moe_dispatch() -> EntryProgram:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from learning_jax_sharding_tpu.ops.moe_dispatch import moe_a2a_ff

    mesh = _mesh24()
    built: dict = {}

    def ensure():
        if built:
            return built
        e, t, m, h = 4, 16, 32, 64
        rng = np.random.default_rng(0)
        sh = NamedSharding(mesh, P("data", None))
        wsh = NamedSharding(mesh, P("data", None, None))
        x = jax.device_put(
            rng.standard_normal((t, m)).astype(np.float32), sh
        )
        probs = jax.device_put(
            jax.nn.softmax(
                jnp.asarray(rng.standard_normal((t, e)), jnp.float32)
            ), sh,
        )
        w_up = jax.device_put(
            rng.standard_normal((e, m, h)).astype(np.float32), wsh
        )
        w_down = jax.device_put(
            rng.standard_normal((e, h, m)).astype(np.float32), wsh
        )

        def fn(x, probs, w_up, w_down):
            return moe_a2a_ff(
                x, probs, w_up, w_down, mesh=mesh, ep_axis="data",
                top_k=2, capacity_factor=1.25, dtype=jnp.float32,
            )

        built.update(fn=fn, args=(x, probs, w_up, w_down))
        return built

    def hlo():
        b = ensure()
        return compiled_hlo(b["fn"], *b["args"])

    def shardflow():
        from learning_jax_sharding_tpu.analysis.shardflow import (
            trace_shardflow,
        )

        b = ensure()
        return trace_shardflow(
            "moe_dispatch", b["fn"], *b["args"], mesh=mesh
        )

    return EntryProgram("moe_dispatch", mesh, hlo, shardflow=shardflow)


def _seq_attention(name: str) -> EntryProgram:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh24()
    built: dict = {}

    def ensure():
        if built:
            return built
        from learning_jax_sharding_tpu.ops.ring_attention import (
            ring_attention,
        )
        from learning_jax_sharding_tpu.ops.ulysses import ulysses_attention

        b, s, n, h = 2, 32, 4, 16
        rng = np.random.default_rng(0)
        sh = NamedSharding(mesh, P("data", "model", None, None))
        q, k, v = (
            jax.device_put(
                rng.standard_normal((b, s, n, h)).astype(np.float32), sh
            )
            for _ in range(3)
        )
        op = ring_attention if name == "ring_attention" else ulysses_attention

        def fn(q, k, v):
            return op(
                q, k, v, mesh=mesh, axis="model", causal=True,
                batch_axis="data",
            )

        built.update(fn=fn, args=(q, k, v))
        return built

    def hlo():
        b = ensure()
        return compiled_hlo(b["fn"], *b["args"])

    def shardflow():
        from learning_jax_sharding_tpu.analysis.shardflow import (
            trace_shardflow,
        )

        b = ensure()
        return trace_shardflow(name, b["fn"], *b["args"], mesh=mesh)

    return EntryProgram(name, mesh, hlo, shardflow=shardflow)


#: Entry points the layout search (``analysis.layout_search``) knows how to
#: re-search — a subset of :func:`build_entry_programs` names, audited as
#: such by ``tests/test_shardcheck.py`` (a search-emitted contract must name
#: a real entry point, and every searchable name must have a golden to be
#: diffed against). train/ZeRO-1 search the param-tree (+ optimizer-state:
#: the 2004.13336 weight-update space) axis choices; the engine families
#: search the params + KV-cache layouts of the live dispatch args.
SEARCHABLE_ENTRIES: tuple[str, ...] = (
    "train_step", "zero1_update", "mixed_step", "multi_step",
)


def build_search_inputs(name: str, mesh: Any = None) -> dict:
    """The layout search's view of one searchable entry point: the SAME
    builders the contract pass compiles, returned pre-compile as
    ``{name, fn, args, kwargs, mesh, rules, while_trip_hint,
    vary_paths}`` — ``fn(*args)`` carries its hand-tuned shardings on
    the committed argument leaves (the search's incumbent), and
    ``vary_paths`` restricts the searched leaves by tree-path substring
    (None = every float tensor of rank >= 2, the engine case: params +
    KV cache)."""
    if name not in SEARCHABLE_ENTRIES:
        raise ValueError(
            f"unknown searchable entry point {name!r}; "
            f"known: {sorted(SEARCHABLE_ENTRIES)}"
        )
    mesh = mesh if mesh is not None else _mesh24()
    if name in ("train_step", "zero1_update"):
        zero1 = "data" if name == "zero1_update" else None
        cfg, state, batch, step, rules = _train_state_and_step(
            mesh, zero1_axis=zero1
        )
        return dict(
            name=name, fn=step.jitted, args=(state, batch), kwargs={},
            mesh=mesh, rules=rules, while_trip_hint=None,
            # ZeRO-1 additionally searches the optimizer-state leaves —
            # how the weight update shards over the data axis is the
            # 2004.13336 search space; plain train_step fixes the
            # moments to mirror the params and searches params only.
            vary_paths=(
                (".params", ".opt_state") if zero1 else (".params",)
            ),
        )
    # mixed_step / multi_step: a live tiny engine, same construction as
    # _engine_programs(mixed=True[, horizon=4]) — one short serve
    # populates the dispatch-arg caches, then the search re-simulates
    # that program's jaxpr per candidate layout (no candidate compiles).
    from learning_jax_sharding_tpu.models.serving import ContinuousEngine
    from learning_jax_sharding_tpu.models.transformer import Transformer
    from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING

    cfg = _tiny_cfg()
    params = _sharded_serving_params(Transformer(cfg), mesh, RULES_TP_SERVING)
    kwargs: dict = dict(mixed=True)
    if name == "multi_step":
        kwargs["horizon"] = 4
    eng = ContinuousEngine(
        cfg, mesh, RULES_TP_SERVING,
        batch_size=2, max_new_tokens=8, refill_chunk=16,
        decode_block_steps=4, **kwargs,
    )
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in (20, 5)
    ]
    eng.serve(params, prompts)
    prog = eng.program(name)
    fn, args = prog.fn, prog.last_args()
    hint = (
        int(eng.horizon) if name == "multi_step" else int(eng._block_steps)
    )
    return dict(
        name=name, fn=fn, args=tuple(args), kwargs={}, mesh=mesh,
        rules=RULES_TP_SERVING, while_trip_hint=hint, vary_paths=None,
    )


def build_entry_programs(names: list[str] | None = None) -> list[EntryProgram]:
    """All contract-checkable programs (or the named subset), lazily
    compiled. Must run under the 8-device emulated mesh (the CLI forces
    it; tests inherit conftest's)."""
    programs: list[EntryProgram] = [
        _train_like("train_step"),
        # The watchdog regime: fit(watchdog=...) forces with_grad_norm,
        # whose global-norm epilogue adds collectives — its own golden,
        # or fit(contract=..., watchdog=...) could never launch.
        _train_like("train_step_gn", with_grad_norm=True, audit=False),
        # The resilience regime: fit(resilience=...) compiles the
        # on-device non-finite guard (update gated by
        # isfinite(loss + grad_norm) selects). The guard is supposed to
        # add NO collectives over train_step_gn, but XLA's layout/CSE
        # differs slightly once the selects are in — its own golden pins
        # the actual program, so fit(contract=, resilience=) launches
        # against what it really runs.
        _train_like(
            "train_step_skip", with_grad_norm=True, skip_nonfinite=True,
            audit=False,
        ),
        _train_like("zero1_update", zero1_axis="data"),
        _zero1_q8(),
        *_serving_programs(),
        *_kv_transfer_programs(),
        *_kv_page_programs(),
        *_kv_page_programs(compression=True),
        *_swap_reshard_programs(),
        _moe_dispatch(),
        _seq_attention("ring_attention"),
        _seq_attention("ulysses_attention"),
    ]
    if names:
        unknown = set(names) - {p.name for p in programs}
        if unknown:
            raise ValueError(
                f"unknown entry point(s) {sorted(unknown)}; "
                f"known: {sorted(p.name for p in programs)}"
            )
        programs = [p for p in programs if p.name in names]
    return programs
