"""Published keys of a GPT-2-shaped config (Cerebras-GPT's ``config.json``)
-> ``GPTForCausalLM`` under ``jit.TrainStep``: AdamW, global-norm clip,
bfloat16 AMP-O2 with float32 master weights, ``HealthGuard`` on — the train
phase of ``chip_smoke.py`` at this model's sizes.  The training knobs come
from the traffic file's ``train`` group."""

from __future__ import annotations

from benchmark.lib import program, training
from benchmark.reference import gpt2_like


def gpt_config(config: dict, recompute: bool):
    from paddle_tpu.models import GPTConfig

    return GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_hidden_layers=config["n_layer"],
        num_attention_heads=config["n_head"],
        intermediate_size=config["n_inner"],
        max_position_embeddings=config["n_positions"],
        layer_norm_eps=config["layer_norm_epsilon"],
        dropout=config["resid_pdrop"],
        initializer_range=config["initializer_range"], recompute=recompute)


def reference_weights(model) -> dict:
    g = model.gpt

    def layer(i):
        b = g.h[i]
        hid = b.qkv_proj.weight.value.shape[0]
        # qkv_proj's output is reshaped [.., 3, heads, d]: thirds are q, k, v
        w = b.qkv_proj.weight.value.reshape(hid, 3, -1)
        bias = b.qkv_proj.bias.value.reshape(3, -1)
        out = {"wo": b.out_proj.weight.value, "bo": b.out_proj.bias.value,
               "w_in": b.fc_in.weight.value, "b_in": b.fc_in.bias.value,
               "w_out": b.fc_out.weight.value, "b_out": b.fc_out.bias.value,
               "ln_1": (b.ln_1.weight.value, b.ln_1.bias.value),
               "ln_2": (b.ln_2.weight.value, b.ln_2.bias.value)}
        for j, n in enumerate("qkv"):
            out["w" + n], out["b" + n] = w[:, j], bias[j]
        return out

    return {"wte": g.wte.weight.value, "wpe": g.wpe.weight.value,
            "layer": layer, "ln_f": (g.ln_f.weight.value, g.ln_f.bias.value)}


class System:
    chips = 1

    def __init__(self, config: dict, traffic: dict, seed: int):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.distributed.health import HealthGuard, HealthPolicy
        from paddle_tpu.models import GPTForCausalLM

        self.config, t = config, traffic["train"]
        self.batch_size, self.seq = t["batch"], t["seq"]
        self.tokens_per_step = self.batch_size * self.seq
        self.token_ids = config.get("token_id_limit", config["vocab_size"])
        self.check_seq = traffic["check"]["seq"]
        cfg = gpt_config(config, t["recompute"])
        self.model = program.construct(
            lambda: paddle.amp.decorate(GPTForCausalLM(cfg), level="O2",
                                        dtype=config["dtype"]), seed)
        opt = paddle.optimizer.AdamW(
            t["learning_rate"], parameters=self.model.parameters(),
            grad_clip=nn.ClipGradByGlobalNorm(t["clip_global_norm"]),
            multi_precision=True)
        self.guard = HealthGuard(HealthPolicy(), name="benchmark",
                                 on_escalate="raise") \
            if t["health_guard"] else None
        self.step = paddle.jit.TrainStep(
            self.model, lambda m, x, y: m(x, labels=y)[0], opt,
            health_guard=self.guard)

    def batch(self, rng):
        return training.random_batch(rng, self.token_ids, self.batch_size,
                                     self.seq)

    def check(self, rng) -> dict:
        return training.reference_check(
            self.model, self.config, gpt2_like, reference_weights(self.model),
            rng, self.check_seq, self.token_ids)

    def finish(self) -> dict:
        if self.guard is None:
            return {"ok": True}
        self.guard.flush()
        return {"steps_skipped": self.guard.steps_skipped,
                "rewinds": self.guard.rewinds,
                "ok": self.guard.steps_skipped == 0
                and self.guard.rewinds == 0}


def build(config: dict, traffic: dict, seed: int, devices) -> System:
    return System(config, traffic, seed)
