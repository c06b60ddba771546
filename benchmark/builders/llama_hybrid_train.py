"""Published keys of a Llama-shaped config -> ``LlamaForCausalLMHybrid``
under ``DistributedTrainStep`` on the mesh the traffic file names (``mesh``:
degrees for ``topology.build_mesh``), built as ``chip_smoke.py``'s
``four_chips`` builds it: AdamW, global-norm clip, bfloat16 AMP-O2, the
ZeRO stage the traffic file gives.  How the program splits the one global
batch it is handed is the program's business.

The weights are made by the program's own constructors, eagerly: the hybrid
model reads each leaf's placement while it stacks its layers over ``pipe``,
which a traced construction (``lib.program.construct``) would hide."""

from __future__ import annotations

from benchmark.builders import llama_serve
from benchmark.lib import training
from benchmark.reference import llama_like


def reference_weights(model) -> dict:
    """``ScannedLayers`` stacks every leaf of its layers into ``[L, ...]``
    under the leaf's dotted name with ``__`` for the dots."""
    stack = dict(model.decoder.named_parameters())

    def leaf(name, i):
        return stack[name.replace(".", "__")].value[i]

    def layer(i):
        names = {"wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
                 "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
                 "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
                 "w_down": "mlp.down_proj.weight",
                 "ln_attn": "input_layernorm.weight",
                 "ln_mlp": "post_attention_layernorm.weight"}
        return {k: leaf(n, i) for k, n in names.items()}

    return {"embed": model.embed_tokens.weight.value, "layer": layer,
            "norm": model.norm.weight.value,
            "head": model.lm_head.weight.value}


class System:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        import paddle_tpu as paddle
        import paddle_tpu.distributed as dist
        import paddle_tpu.nn as nn
        from paddle_tpu.distributed import topology
        from paddle_tpu.models.llama_parallel import LlamaForCausalLMHybrid

        self.config, t = config, traffic["train"]
        self.chips = len(devices)
        self.batch_size, self.seq = t["batch"], t["seq"]
        self.tokens_per_step = self.batch_size * self.seq
        self.vocab = config["vocab_size"]
        self.check_seq = traffic["check"]["seq"]
        self.hcg = topology.HybridCommunicateGroup(
            mesh=topology.build_mesh(devices=devices, **traffic["mesh"]))
        topology.set_hybrid_communicate_group(self.hcg)
        paddle.seed(int(seed) % (1 << 31))
        model = LlamaForCausalLMHybrid(llama_serve.llama_config(config),
                                       self.hcg)
        opt = paddle.optimizer.AdamW(
            t["learning_rate"], parameters=model.parameters(),
            grad_clip=nn.ClipGradByGlobalNorm(t["clip_global_norm"]))
        self.model, opt = paddle.amp.decorate(model, opt, level="O2",
                                              dtype=config["dtype"])
        self.step = dist.DistributedTrainStep(
            self.model, lambda m, x, y: m(x, labels=y)[0], opt, self.hcg,
            sharding_stage=t["sharding_stage"])

    def batch(self, rng):
        return training.random_batch(rng, self.vocab, self.batch_size,
                                     self.seq)

    def check(self, rng) -> dict:
        return training.reference_check(
            self.model, self.config, llama_like,
            reference_weights(self.model), rng, self.check_seq, self.vocab)

    def finish(self) -> dict:
        """Every parameter lives on every chip of the mesh, and each axis
        the mesh splits is named by some parameter's placement."""
        want = {a for a, n in self.hcg.mesh.shape.items() if n > 1}
        used, everywhere = set(), True
        devices = set(self.hcg.mesh.devices.flat)
        for _, p in self.model.named_parameters():
            sh = p.value.sharding
            everywhere &= set(sh.device_set) == devices
            for entry in getattr(sh, "spec", ()):
                if entry is not None:
                    used |= set(entry if isinstance(entry, tuple)
                                else (entry,))
        missing = sorted(want - used - {"data"})
        return {"axes_unused": missing, "on_every_chip": everywhere,
                "ok": everywhere and not missing}


def build(config: dict, traffic: dict, seed: int, devices) -> System:
    return System(config, traffic, seed, devices)
