"""What the training builders share: the seeded batch and the check of the
model's logits and loss against the plain reference."""

from __future__ import annotations

import numpy as np

from . import checks, generators, program


def random_batch(rng, token_ids: int, batch: int, seq: int):
    """Uniform token ids made on the host, labels shifted by one, fed
    through ``paddle.to_tensor`` as a user's input pipeline would."""
    import paddle_tpu as paddle

    ids = rng.integers(0, token_ids, (batch, seq)).astype("int32")
    return paddle.to_tensor(ids), paddle.to_tensor(np.roll(ids, -1, axis=1))


def reference_check(model, config: dict, reference, weights: dict, rng,
                    seq: int, token_ids: int) -> dict:
    """At the seeded weights, before the first step: the model's logits and
    loss on one short seeded sequence against ``reference`` (a module of
    ``benchmark/reference``) over ``weights`` in its layout."""
    import paddle_tpu as paddle

    ids = generators.tokens(rng, seq, token_ids)
    labels = np.roll(ids, -1)
    forward = program.static_forward(model, lambda m, x, y: m(x, labels=y))
    with paddle.no_grad():
        loss, logits = forward(paddle.to_tensor(ids[None]),
                               paddle.to_tensor(labels[None]))
    ref = np.asarray(reference.logits(weights, config, ids))
    ref_loss = reference.loss_of(ref, labels)
    verdict = checks.logits_agree(
        checks.row_errors(np.asarray(logits.value[0], np.float32), ref),
        config["check"]["logit_rms_tol"])
    verdict.update(loss=float(loss), reference_loss=ref_loss,
                   loss_abs_err=abs(float(loss) - ref_loss))
    verdict["limits"]["loss_abs_err"] = checks.LOSS_TOL
    return checks.decide(verdict)
