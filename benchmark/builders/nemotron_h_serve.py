"""Published keys of a ``nemotron_h`` config (NVIDIA-Nemotron-3-Nano-30B-A3B's
``config.json``) -> ``NemotronHForCausalLM`` in bfloat16 behind a
``ServingEngine``, as one chip's share of an expert-parallel deployment: the
file's ``experts_held`` names the routed experts this chip holds
(``n_routed_experts`` counts them), ``router_experts`` is the router's
published width, ``vocab_size`` the slice of the vocabulary.

The engine's knobs (``max_batch``, ``page_tokens``, ``max_pages_per_seq``,
``num_pages``, ``max_queue``) are the deployment: they come from the traffic
file's ``engine`` group, where no later PR can tune them."""

from __future__ import annotations

import gc

import numpy as np

from benchmark.lib import checks, program, serving
from benchmark.reference import nemotron_h

# checks.NEAR_TIE is an absolute 0.25 set at Mistral's logits rms of 1.3: the
# limit is held relative to the rms of the reference's own logits
NEAR_TIE_AT_RMS = 1.3


def nemotron_config(config: dict):
    """Every field of ``NemotronHConfig`` the file states, under the
    published key's own name; the router keeps its published width."""
    import dataclasses

    from paddle_tpu.models import NemotronHConfig

    keys = {f.name for f in dataclasses.fields(NemotronHConfig)}
    return NemotronHConfig(**dict(
        {k: v for k, v in config.items() if k in keys},
        n_routed_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"])))


def reference_weights(model) -> dict:
    """The model's parameters in the reference's layout; a block's arrays
    are handed over as they are and cast there, one block at a time."""
    base = model.backbone

    def layer(i):
        blk = base.layers[i]
        m, out = blk.mixer, {"ln": blk.norm.weight.value}
        if blk.kind == "M":
            out.update(w_in=m.in_proj.weight.value,
                       conv_w=m.conv_weight.value, conv_b=m.conv_bias.value,
                       A_log=m.A_log.value, dt_bias=m.dt_bias.value,
                       D=m.D.value, norm_w=m.norm_weight.value,
                       w_out=m.out_proj.weight.value)
        elif blk.kind == "*":
            out.update(wq=m.q_proj.weight.value, wk=m.k_proj.weight.value,
                       wv=m.v_proj.weight.value, wo=m.o_proj.weight.value)
        else:
            e, s = m.experts, m.shared_experts
            out.update(w_router=e.gate_weight.value,
                       router_bias=e.e_score_correction_bias.value,
                       e_up=e.up_proj.value, e_down=e.down_proj.value,
                       s_up=s.up_proj.weight.value,
                       s_down=s.down_proj.weight.value)
        return out

    return {"embed": base.embeddings.weight.value, "layer": layer,
            "norm": base.norm_f.weight.value,
            "head": model.lm_head.weight.value}


class System:
    chips = 1

    def __init__(self, config: dict, traffic: dict, seed: int):
        import paddle_tpu as paddle
        from paddle_tpu.models import NemotronHForCausalLM

        self.config, self.traffic = config, traffic
        self.vocab = config["vocab_size"]
        cfg = nemotron_config(config)

        def factory():
            model = NemotronHForCausalLM(cfg)
            model.eval()
            return paddle.amp.decorate(model, level="O2",
                                       dtype=config["dtype"])

        self.model = program.construct(factory, seed)

    def engine(self, on_token):
        """The engine, with a sink that also keeps what ``verify`` needs of
        the warm-up's requests, which are served first and alone: the
        experts the PROGRAMS chose for each of their tokens, copied from
        where the launches left them on the device (``last_prefill_kept``:
        [expert blocks, tokens, k] with -1 on the padding;
        ``last_decode_kept``: [expert blocks, rows, 1, k], one row live),
        and each request's SSM state one step before its last token, while
        the request still holds its row."""
        import weakref

        from paddle_tpu.serving import ServingEngine

        check = self.traffic["check"]
        self.check_routes = routes = {}     # rid -> [expert blocks, k] a
        # token, the prompt's first, in the order of the sequence
        self.check_states = states = []

        def sink(rid, idx, tok):
            if rid in routes or len(routes) < check["prompts"]:
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
                if idx == check["new_tokens"] - 2:
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
        released.  Three things are held, each by a limit of the file's
        ``check``: the decode logits by ``lib/checks``' own rule against a
        reference that FOLLOWS the programs' expert choices (a bfloat16
        hidden state flips a near-tie of ``s + b`` now and then, and a row
        whose token took another held expert is off by an expert's whole
        output, which says nothing of the arithmetic); the choices
        themselves against the reference's own (the share of compared
        (token, expert block) pairs that differ, and how near a tie each
        followed choice is); and the recurrent state of the FIRST Mamba-2
        block against the reference scan's own, head by head (a state kept
        in bfloat16 hides in the logits)."""
        gc.collect()
        weights = reference_weights(self.model)
        routes = iter(self.check_routes.values())
        rms, ref_states, widths, prompt_widths, flips = [], [], [], [], []

        def reference(ids, pos):
            # the last generated token was fed to no program; the state one
            # token before the end is what the engine's row held when the
            # sink copied it
            forced = np.stack(next(routes)).transpose(1, 0, 2)
            assert forced.shape[1] == len(ids), "a choice a token"
            own, width = [], []
            out, states = nemotron_h.logits_and_states(
                weights, self.config, ids, pos, state_after=len(ids) - 1,
                choices=own, forced=forced, tie_widths=width)
            out = np.asarray(out)
            rms.append(float(np.sqrt(np.mean(out.astype(np.float64) ** 2))))
            ref_states.append(np.asarray(states))
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
        verdict["route_pairs"] = int(flips.size)
        verdict["route_flips"] = int(flips.sum())
        verdict["route_flip_share"] = float(flips.mean())
        verdict["route_tie_width_worst"] = float(widths.max())
        # for the record: the prompts' other tokens, followed too
        verdict["route_tie_width_prompt_worst"] = float(max(prompt_widths))

        def rows(got, ref):     # rms error of got[i] over ref[i]'s rms
            return checks.row_errors(got.reshape(len(got), -1),
                                     ref.reshape(len(ref), -1))

        # past the first blocks the bfloat16 activations' own noise hides
        # the state's precision: whole layers are for the record.  In the
        # first state block a head's error (rms over its [P, N] state, over
        # the reference's) is 0.4-1.2 % from the bfloat16 activations alone,
        # and its WORST head is an extreme value that a bfloat16 state's
        # reading overlaps; the MEAN over the heads of every check prompt
        # moves by a third with a bfloat16 state and by a thirtieth between
        # seeds: it carries the limit
        pairs = list(zip(self.check_states, ref_states))
        by_layer = np.median([rows(got, ref) for got, ref in pairs], 0)
        verdict["state_rms_rel_err_first_layer"] = float(by_layer[0])
        verdict["state_rms_rel_err_last_layer"] = float(by_layer[-1])
        heads = np.stack([rows(got[0], ref[0]) for got, ref in pairs])
        verdict["state_head_rms_rel_err_worst"] = float(heads.max())
        verdict["state_head_rms_rel_err_mean"] = float(heads.mean())
        verdict["ref_logits_rms"] = float(np.mean(rms))
        verdict["near_tie_limit"] = checks.NEAR_TIE \
            * min(verdict["ref_logits_rms"] / NEAR_TIE_AT_RMS, 1.0)
        verdict["limits"].update(
            short_of_best=verdict["near_tie_limit"],
            state_head_rms_rel_err_mean=check["state_head_rms_tol"],
            route_flip_share=check["route_flip_share"],
            route_tie_width_worst=check["route_tie"])
        return checks.decide(verdict)


def build(config: dict, traffic: dict, seed: int, devices) -> System:
    return System(config, traffic, seed)
