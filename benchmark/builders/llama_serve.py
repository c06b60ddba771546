"""Published keys of a Llama-shaped config (Mistral's ``config.json``) ->
``LlamaForCausalLM`` in bfloat16 behind a ``ServingEngine``.

The engine's knobs (``max_batch``, ``page_tokens``, ``max_pages_per_seq``,
``num_pages``, ``max_queue``) are the deployment: they come from the traffic
file's ``engine`` group, where no later PR can tune them."""

from __future__ import annotations

import gc

from benchmark.lib import program, serving
from benchmark.reference import llama_like


def llama_config(config: dict):
    from paddle_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        tie_word_embeddings=config["tie_word_embeddings"],
        initializer_range=config["initializer_range"], recompute=False)


def reference_weights(model) -> dict:
    """The model's parameters in the reference's layout; a layer's arrays
    are handed over as they are and cast there, one layer at a time."""
    base = model.llama

    def layer(i):
        blk = base.layers[i]
        a, m = blk.self_attn, blk.mlp
        return {"wq": a.q_proj.weight.value, "wk": a.k_proj.weight.value,
                "wv": a.v_proj.weight.value, "wo": a.o_proj.weight.value,
                "w_gate": m.gate_proj.weight.value,
                "w_up": m.up_proj.weight.value,
                "w_down": m.down_proj.weight.value,
                "ln_attn": blk.input_layernorm.weight.value,
                "ln_mlp": blk.post_attention_layernorm.weight.value}

    head = model.lm_head.weight.value if model.lm_head is not None \
        else base.embed_tokens.weight.value.T
    return {"embed": base.embed_tokens.weight.value, "layer": layer,
            "norm": base.norm.weight.value, "head": head}


class System:
    chips = 1

    def __init__(self, config: dict, traffic: dict, seed: int):
        import paddle_tpu as paddle
        from paddle_tpu.models import LlamaForCausalLM

        self.config, self.traffic = config, traffic
        self.vocab = config["vocab_size"]
        cfg = llama_config(config)

        def factory():
            model = LlamaForCausalLM(cfg)
            model.eval()
            return paddle.amp.decorate(model, level="O2",
                                       dtype=config["dtype"])

        self.model = program.construct(factory, seed)

    def engine(self, on_token):
        from paddle_tpu.serving import ServingEngine

        return ServingEngine(self.model, on_token=on_token,
                             prefix_cache=bool(self.traffic.get(
                                 "prefix_cache", False)),
                             **self.traffic["engine"])

    def verify(self, sample) -> dict:
        """After the window, with the engine and its pool released."""
        gc.collect()
        weights = reference_weights(self.model)
        return serving.compare_with_reference(
            sample, lambda ids, pos: llama_like.logits(
                weights, self.config, ids, pos),
            self.config["check"]["logit_rms_tol"])


def build(config: dict, traffic: dict, seed: int, devices) -> System:
    return System(config, traffic, seed)
