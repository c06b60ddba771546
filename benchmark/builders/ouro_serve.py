"""Published keys of an ``ouro`` config (Ouro-2.6B's ``config.json``) ->
``OuroForCausalLM`` in bfloat16 behind a ``ServingEngine``, uncut: every
layer, every pass, the whole vocabulary.

The engine's knobs (``max_batch``, ``page_tokens``, ``max_pages_per_seq``,
``num_pages``, ``max_queue``) are the deployment: they come from the traffic
file's ``engine`` group, where no later PR can tune them.

The weights are made by :func:`construct`, one small program a parameter
shape, not by ``program.construct``'s one traced call of every initializer
(several hundred random fills at 48 layers, 95 s of compile on the v5e)."""

from __future__ import annotations

import gc

import numpy as np

from benchmark.lib import checks, program, serving
from benchmark.reference import ouro


def ouro_config(config: dict):
    """Every field of ``OuroConfig`` the file states, under the published
    key's own name."""
    import dataclasses

    from paddle_tpu.models import OuroConfig

    keys = {f.name for f in dataclasses.fields(OuroConfig)}
    return OuroConfig(**{k: v for k, v in config.items() if k in keys})


def construct(factory, seed: int, std: float):
    """The model ``factory()`` builds, its weights made on the device from
    ``seed`` as the model's initializers make them: normal(0, ``std``) for
    every matrix and the embedding, 1 for a norm's scale, 0 for a bias.

    The structure is traced once; the buffers (the rope tables: no random
    fill) come from that one call; each parameter comes from a program of
    its shape and type, compiled once and called with the parameter's own
    fold of the seed's key."""
    import functools

    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework import key_scope

    box = {}

    def make(key):
        with key_scope(key):
            box["model"] = m = factory()
        return [b.value for b in m.buffers()]

    key = program.seed_key(seed)
    buffers = jax.jit(make)(key)
    model = box["model"]
    for b, v in zip(model.buffers(), buffers):
        b.set_value(v)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def fill(k, shape, dtype, kind):
        if kind == "normal":
            return (jax.random.normal(k, shape, jnp.float32) * std
                    ).astype(dtype)
        return jnp.full(shape, kind == "one", dtype)

    for i, (name, p) in enumerate(model.named_parameters()):
        kind = ("zero" if name.endswith("bias") else
                "one" if "norm" in name.rsplit(".", 2)[-2] else "normal")
        p.set_value(fill(jax.random.fold_in(key, i), tuple(p.value.shape),
                         jnp.dtype(p.value.dtype), kind))
    return model


def reference_weights(model) -> dict:
    """The model's parameters in the reference's layout; a layer's arrays
    are handed over as they are and cast there, one layer at a time."""
    base = model.ouro

    def layer(i):
        blk = base.layers[i]
        a, m = blk.self_attn, blk.mlp
        return {"wq": a.q_proj.weight.value, "wk": a.k_proj.weight.value,
                "wv": a.v_proj.weight.value, "wo": a.o_proj.weight.value,
                "w_gate": m.gate_proj.weight.value,
                "w_up": m.up_proj.weight.value,
                "w_down": m.down_proj.weight.value,
                "ln_attn": blk.input_layernorm.weight.value,
                "ln_attn_2": blk.input_layernorm_2.weight.value,
                "ln_mlp": blk.post_attention_layernorm.weight.value,
                "ln_mlp_2": blk.post_attention_layernorm_2.weight.value}

    return {"embed": base.embed_tokens.weight.value, "layer": layer,
            "norm": base.norm.weight.value,
            "gate_w": base.early_exit_gate.weight.value,
            "gate_b": base.early_exit_gate.bias.value,
            "head": model.lm_head.weight.value}


class System:
    chips = 1

    def __init__(self, config: dict, traffic: dict, seed: int):
        import paddle_tpu as paddle
        from paddle_tpu.models import OuroForCausalLM

        self.config, self.traffic = config, traffic
        self.vocab = config["vocab_size"]
        cfg = ouro_config(config)

        def factory():
            model = OuroForCausalLM(cfg)
            model.eval()
            return paddle.amp.decorate(model, level="O2",
                                       dtype=config["dtype"])

        self.model = construct(factory, seed, cfg.initializer_range)

    def engine(self, on_token):
        from paddle_tpu.serving import ServingEngine

        return ServingEngine(self.model, on_token=on_token,
                             **self.traffic["engine"])

    def verify(self, sample, **control) -> dict:
        """After the window, with the engine and its pool released: every
        decode row of the check prompts against the float32 reference's
        full forward (4 passes x every layer over prompt + generated
        tokens), and every greedy choice a near-tie of the reference's best.
        ``control``: keyword arguments of ``reference.ouro.logits`` that
        make the reference depart from the published model
        (``ouro.control_kwargs``): the verdict must then be refused."""
        gc.collect()
        weights = reference_weights(self.model)
        rms = []

        def reference(ids, pos):
            out = np.asarray(ouro.logits(weights, self.config, ids, pos,
                                         **control))
            rms.append(float(np.sqrt(np.mean(out.astype(np.float64) ** 2))))
            return out

        check = self.config["check"]
        verdict = serving.compare_with_reference(sample, reference,
                                                 check["logit_rms_tol"])
        verdict["ref_logits_rms"] = float(np.mean(rms))
        verdict["limits"]["short_of_best"] = check["near_tie"]
        return checks.decide(verdict)


def build(config: dict, traffic: dict, seed: int, devices) -> System:
    return System(config, traffic, seed)
