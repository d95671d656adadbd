"""Did a change move a program no cell of it should move? Hashes, at tiny
sizes and on the CPU's lowering, the StableHLO of a GPT-2-shaped train step
(``sharded_train_state`` + ``make_train_step`` + ``default_optimizer``, as
``benchmark/train.py`` builds it), of both served forms of ``DroplessMoE``
(joyai's gated one, nemotron's held ungated latent one), their seeded initial
values, and the train state after two steps. Run it in two checkouts and
compare the lines (PR 35: byte-identical at the parent and the change).

    JAX_PLATFORMS=cpu python scripts/lowered_hash.py
    (cd _parent && JAX_PLATFORMS=cpu python ../scripts/lowered_hash.py)
"""
import hashlib, sys, dataclasses
sys.path.insert(0, ".")
import jax, jax.numpy as jnp, numpy as np
from learning_jax_sharding_tpu.models.transformer import CONFIG_TINY, Transformer, TransformerConfig, fused_next_token_loss
from learning_jax_sharding_tpu.parallel import single_device_mesh, mesh_sharding, put
from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
from learning_jax_sharding_tpu.training.loop import TrainLoopConfig, default_optimizer
from learning_jax_sharding_tpu.training.pipeline import make_train_step, sharded_train_state
def h(t): return hashlib.sha256(t.encode()).hexdigest()[:16]
mesh = single_device_mesh()
cfg = dataclasses.replace(CONFIG_TINY, use_bias=True, remat=True, max_seq_len=128)
tok = np.zeros((2, 129), np.int32)
sh = mesh_sharding(mesh, "data", None)
batch = {"inputs": put(tok[:, :-1], sh), "targets": put(tok[:, 1:], sh)}
opt = default_optimizer(TrainLoopConfig(steps=10, global_batch_size=2, warmup_steps=3))
state, ssh = sharded_train_state(Transformer(cfg), opt, batch["inputs"], {"params": jax.random.key(0)}, mesh, RULES_DP_TP)
step = make_train_step(ssh, {k: v.sharding for k, v in batch.items()}, mesh, RULES_DP_TP, loss_fn=fused_next_token_loss, loss_needs_params=True, apply_kwargs={"return_hidden": True})
from learning_jax_sharding_tpu.parallel.logical import activate
with activate(mesh, RULES_DP_TP):
    print("train_step", h(step.jitted.lower(state, batch).as_text()))
# a dropless layer's forward (joyai form, pallas interpreted off) and nemotron's held ungated form
from learning_jax_sharding_tpu.models.moe import DroplessMoE
x = jnp.zeros((2, 16, 64), jnp.bfloat16)
def form(name, **kw):
    layer = DroplessMoE(features=64, hidden=32, num_experts=8, top_k=2, experts="pallas", dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **kw)
    p = jax.eval_shape(layer.init, jax.random.key(0), x)
    print(name, h(jax.jit(layer.apply).lower(p, x).as_text()))
    vals = jax.tree.leaves(jax.jit(layer.init)(jax.random.key(0), x))
    print(name, "init_values", h("".join(hashlib.sha256(np.asarray(v.astype(jnp.float32))).hexdigest() for v in vals)))
form("joyai_form", shared_experts=1)
form("nemotron_form", shared_experts=1, gated=False, latent=32, held=(0, 4), centred_down=True, expert_init_scale=0.08)
# the train state's own values after two steps (the optimizer path through sharded_train_state)
for _ in range(2):
    state, loss = step(state, batch)
print("state_after_2", h("".join(hashlib.sha256(np.asarray(v)).hexdigest() for v in jax.tree.leaves(state.params))), float(loss))
