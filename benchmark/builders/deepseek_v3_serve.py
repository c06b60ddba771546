"""Published keys of a ``deepseek_v3`` config (DeepSeek-V3's ``config.json``)
-> ``DeepseekV3ForCausalLM`` in bfloat16 behind a ``ServingEngine``, as one
chip's share of an expert-parallel deployment: the file's ``experts_held``
names the routed experts this chip holds (``n_routed_experts`` counts them),
``router_experts`` is the router's published width, ``vocab_size`` the slice
of the vocabulary.

The engine's knobs (``max_batch``, ``page_tokens``, ``max_pages_per_seq``,
``num_pages``, ``max_queue``) are the deployment: they come from the traffic
file's ``engine`` group, where no later PR can tune them."""

from __future__ import annotations

import gc

import numpy as np

from benchmark.lib import checks, program, serving
from benchmark.reference import deepseek_v3


def deepseek_config(config: dict):
    """Every field of ``DeepseekV3Config`` the file states, under the
    published key's own name; the router keeps its published width."""
    import dataclasses

    from paddle_tpu.models import DeepseekV3Config

    keys = {f.name for f in dataclasses.fields(DeepseekV3Config)}
    return DeepseekV3Config(**dict(
        {k: v for k, v in config.items() if k in keys},
        n_routed_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"])))


def reference_weights(model) -> dict:
    """The model's parameters in the reference's layout; a layer's arrays
    are handed over as they are and cast there, one layer at a time."""
    base = model.model

    def mlp(m, prefix):
        return {prefix + "gate": m.gate_proj.weight.value,
                prefix + "up": m.up_proj.weight.value,
                prefix + "down": m.down_proj.weight.value}

    def layer(i):
        blk = base.layers[i]
        a = blk.self_attn
        out = {"ln_attn": blk.input_layernorm.weight.value,
               "ln_mlp": blk.post_attention_layernorm.weight.value,
               "w_dq": a.q_a_proj.weight.value,
               "ln_q": a.q_a_layernorm.weight.value,
               "w_uq": a.q_b_proj.weight.value,
               "w_dkv": a.kv_a_proj_with_mqa.weight.value,
               "ln_kv": a.kv_a_layernorm.weight.value,
               "w_ukv": a.kv_b_proj.weight.value,
               "wo": a.o_proj.weight.value}
        if not blk.is_moe:
            return dict(out, **mlp(blk.mlp, "w_"))
        e = blk.mlp.experts
        return dict(out, w_router=e.gate_weight.value,
                    router_bias=e.e_score_correction_bias.value,
                    e_gate=e.gate_proj.value, e_up=e.up_proj.value,
                    e_down=e.down_proj.value,
                    **mlp(blk.mlp.shared_experts, "s_"))

    return {"embed": base.embed_tokens.weight.value, "layer": layer,
            "norm": base.norm.weight.value,
            "head": model.lm_head.weight.value}


class System:
    chips = 1

    def __init__(self, config: dict, traffic: dict, seed: int):
        import paddle_tpu as paddle
        from paddle_tpu.models import DeepseekV3ForCausalLM

        self.config, self.traffic = config, traffic
        self.vocab = config["vocab_size"]
        cfg = deepseek_config(config)

        def factory():
            model = DeepseekV3ForCausalLM(cfg)
            model.eval()
            return paddle.amp.decorate(model, level="O2",
                                       dtype=config["dtype"])

        self.model = program.construct(factory, seed)

    def engine(self, on_token):
        """The engine, with a sink that also keeps what ``verify`` follows:
        the warm-up's requests are served first and alone, and the experts
        the PROGRAMS chose for each of their tokens are copied from where
        the launches left them on the device (``last_prefill_kept``:
        [expert layers, tokens, k] with -1 on the padding;
        ``last_decode_kept``: [expert layers, rows, 1, k], one row live)."""
        import weakref

        from paddle_tpu.serving import ServingEngine

        prompts = self.traffic["check"]["prompts"]
        self.check_routes = routes = {}     # rid -> [expert layers, k] a
        # token, the prompt's first, in the order of the sequence

        def sink(rid, idx, tok):
            if rid in routes or len(routes) < prompts:
                chose = routes.setdefault(rid, [])
                if idx == 0:
                    prompt = eng.last_prefill_kept["moe_choice"]
                    chose.extend(prompt[:, prompt[0, :, 0] >= 0]
                                 .transpose(1, 0, 2))
                else:
                    step = eng.last_decode_kept["moe_choice"][:, :, 0]
                    live, = np.nonzero(step[0, :, 0] >= 0)
                    assert len(live) == 1, "a check request is served alone"
                    chose.append(step[:, live[0]])
            on_token(rid, idx, tok)

        engine = ServingEngine(self.model, on_token=sink,
                               prefix_cache=bool(self.traffic.get(
                                   "prefix_cache", False)),
                               **self.traffic["engine"])
        eng = weakref.proxy(engine)     # verify runs with the engine released
        return engine

    def verify(self, sample) -> dict:
        """After the window, with the engine and its pool released.

        Which experts a token takes is a step function of ``s + b``, and a
        bfloat16 hidden state flips a near-tie now and then; a row whose
        token took another held expert than the float32 reference's is off
        by an expert's whole output, which says nothing of the arithmetic.
        So the reference FOLLOWS the programs' choices at every token of
        the check's sequences (its weights stay its own ``s`` over them),
        the harness's own rule then holds every row (``lib/checks``:
        median, worst at twice the limit, the near-tie), and the choices
        themselves are held to the reference's, at the rows that are
        compared: the share of (token, expert layer) pairs in which they
        differ to ``check.route_flip_share`` (rounding flips one pair in
        seven; a selection by other scores flips most), and each choice to
        being the selection of scores no further than ``check.route_tie``
        from the reference's (``tie_width``: the widest of a run's 480 is
        an extreme value and swings tenfold between seeds, so this limit
        is loose and catches an expert no rounding explains)."""
        gc.collect()
        weights = reference_weights(self.model)
        routes = iter(self.check_routes.values())
        rms, widths, prompt_widths, flips = [], [], [], []

        def reference(ids, pos):
            # the last generated token was fed to no program
            forced = np.stack(next(routes)).transpose(1, 0, 2)
            assert forced.shape[1] == len(ids), "a choice a token"
            own, width = [], []
            out = np.asarray(deepseek_v3.logits(
                weights, self.config, ids, pos, choices=own, forced=forced,
                tie_widths=width))
            rms.append(float(np.sqrt(np.mean(out.astype(np.float64) ** 2))))
            width = np.stack(width)
            widths.append(width[:, pos])
            prompt_widths.append(width[:, :pos[0]].max(initial=0.0))
            flips.append((np.sort(np.stack(own)[:, pos], -1)
                          != np.sort(forced[:, pos], -1)).any(-1))
            return out

        check = self.config["check"]
        verdict = serving.compare_with_reference(
            sample, reference, check["logit_rms_tol"])
        flips, widths = np.concatenate(flips, 1), np.concatenate(widths, 1)
        verdict["ref_logits_rms"] = float(np.mean(rms))
        # (token, expert layer) pairs of the compared rows in which the
        # program took another set of experts than the reference would
        verdict["route_pairs"] = int(flips.size)
        verdict["route_flips"] = int(flips.sum())
        verdict["route_flip_share"] = float(flips.mean())
        verdict["route_tie_width_worst"] = float(widths.max())
        # for the record: the prompts' other tokens, thirty times as many
        # (the widest of so many swings too widely between seeds to carry
        # the limit: PERF.md section 7)
        verdict["route_tie_width_prompt_worst"] = float(max(prompt_widths))
        verdict["limits"].update(
            route_flip_share=check["route_flip_share"],
            route_tie_width_worst=check["route_tie"])
        return checks.decide(verdict)


def build(config: dict, traffic: dict, seed: int, devices) -> System:
    return System(config, traffic, seed)
