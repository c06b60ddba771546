"""Published keys of a ``granitemoehybrid`` config (granite-4.0-h-micro's
``config.json``) -> ``GraniteHybridForCausalLM`` in bfloat16 behind a
``ServingEngine``.

The engine's knobs (``max_batch``, ``page_tokens``, ``max_pages_per_seq``,
``num_pages``, ``max_queue``) are the deployment: they come from the traffic
file's ``engine`` group, where no later PR can tune them."""

from __future__ import annotations

import gc

import numpy as np

from benchmark.lib import checks, program, serving
from benchmark.reference import granite_hybrid

# checks.NEAR_TIE is an absolute 0.25 set at Mistral's logits rms of 1.3; this
# family divides its logits by ``logits_scaling``, so the limit is held
# relative to the rms of the reference's own logits
NEAR_TIE_AT_RMS = 1.3


def granite_config(config: dict):
    """Every field of ``GraniteHybridConfig`` the file states, under the
    published key's own name."""
    import dataclasses

    from paddle_tpu.models import GraniteHybridConfig

    return GraniteHybridConfig(**{
        f.name: config[f.name]
        for f in dataclasses.fields(GraniteHybridConfig) if f.name in config})


def reference_weights(model) -> dict:
    """The model's parameters in the reference's layout; a layer's arrays
    are handed over as they are and cast there, one layer at a time."""
    base = model.model

    def layer(i):
        blk = base.layers[i]
        out = {"ln_in": blk.input_layernorm.weight.value,
               "ln_mlp": blk.post_attention_layernorm.weight.value,
               "w_i": blk.shared_mlp.input_linear.weight.value,
               "w_o": blk.shared_mlp.output_linear.weight.value}
        if blk.kind == "mamba":
            m = blk.mamba
            out.update(w_in=m.in_proj.weight.value,
                       conv_w=m.conv_weight.value, conv_b=m.conv_bias.value,
                       A_log=m.A_log.value, dt_bias=m.dt_bias.value,
                       D=m.D.value, norm_w=m.norm_weight.value,
                       w_out=m.out_proj.weight.value)
        else:
            a = blk.self_attn
            out.update(wq=a.q_proj.weight.value, wk=a.k_proj.weight.value,
                       wv=a.v_proj.weight.value, wo=a.o_proj.weight.value)
        return out

    return {"embed": base.embed_tokens.weight.value, "layer": layer,
            "norm": base.norm.weight.value}


class System:
    chips = 1

    def __init__(self, config: dict, traffic: dict, seed: int):
        import paddle_tpu as paddle
        from paddle_tpu.models import GraniteHybridForCausalLM

        self.config, self.traffic = config, traffic
        self.vocab = config["vocab_size"]
        cfg = granite_config(config)

        def factory():
            model = GraniteHybridForCausalLM(cfg)
            model.eval()
            return paddle.amp.decorate(model, level="O2",
                                       dtype=config["dtype"])

        self.model = program.construct(factory, seed)

    def engine(self, on_token):
        """The engine, with a sink that also keeps what ``verify`` holds the
        recurrent state to: the warm-up's requests are served first and
        alone, and each one's SSM state is copied one step before its last
        token, while the request still holds its row."""
        import weakref

        from paddle_tpu.serving import ServingEngine

        check = self.traffic["check"]
        self.check_states = states = []

        def sink(rid, idx, tok):
            if len(states) < check["prompts"] \
                    and idx == check["new_tokens"] - 2:
                states.append(eng.row_state(rid)["ssm"])
            on_token(rid, idx, tok)

        engine = ServingEngine(self.model, on_token=sink,
                               prefix_cache=bool(self.traffic.get(
                                   "prefix_cache", False)),
                               **self.traffic["engine"])
        eng = weakref.proxy(engine)     # verify runs with the engine released
        return engine

    def verify(self, sample) -> dict:
        """After the window, with the engine, its pool and its row state
        released."""
        gc.collect()
        weights = reference_weights(self.model)
        rms, ref_states = [], []

        def reference(ids, pos):
            # the state one token before the end: what the engine's row
            # held when the sink copied it (generated token j is delivered
            # after the step that consumed token j - 1)
            out, states = granite_hybrid.logits_and_states(
                weights, self.config, ids, pos, state_after=len(ids) - 1)
            out = np.asarray(out)
            rms.append(float(np.sqrt(np.mean(out.astype(np.float64) ** 2))))
            ref_states.append(np.asarray(states))
            return out

        check = self.config["check"]
        verdict = serving.compare_with_reference(
            sample, reference, check["logit_rms_tol"])
        # the recurrent state itself.  rms(got - ref) / rms(ref) of every
        # check prompt's [H, P, N] state, first and last state layer, is
        # for the record: past the first layers the bfloat16 activations'
        # own noise (4-6 % at the last) hides the state's precision.  In the FIRST state layer
        # one layer of activations stands between the tokens and the state,
        # and a head that decays slowly sums a rounding of its state over
        # hundreds of tokens: its worst head is held to a limit
        def rows(got, ref):     # rms error of got[i] over ref[i]'s rms
            return checks.row_errors(got.reshape(len(got), -1),
                                     ref.reshape(len(ref), -1))

        pairs = list(zip(self.check_states, ref_states))
        by_layer = np.median([rows(got, ref) for got, ref in pairs], 0)
        verdict["state_rms_rel_err_first_layer"] = float(by_layer[0])
        verdict["state_rms_rel_err_last_layer"] = float(by_layer[-1])
        verdict["state_head_rms_rel_err_worst"] = float(max(
            rows(got[0], ref[0]).max() for got, ref in pairs))
        # the near-tie, relative to this model's logits
        verdict["ref_logits_rms"] = float(np.mean(rms))
        verdict["near_tie_limit"] = checks.NEAR_TIE \
            * min(verdict["ref_logits_rms"] / NEAR_TIE_AT_RMS, 1.0)
        verdict["limits"].update(
            short_of_best=verdict["near_tie_limit"],
            state_head_rms_rel_err_worst=check["state_head_rms_tol"])
        return checks.decide(verdict)


def build(config: dict, traffic: dict, seed: int, devices) -> System:
    return System(config, traffic, seed)
