"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an error,
never a default: a share of a guessed peak is not a measurement.

The benchmark keeps its own copy (the program has one in
``paddle_tpu/telemetry/collectives.py``) so that no PR to the program can
move a roofline share by editing a peak."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at
    # 819 GB/s per chip.  ICI: 1,600 Gbit/s per chip over 4 links = 50 GB/s a
    # link; the scaling-book's measured one-way figure is 45 GB/s, kept here
    # because it is what a ring all-reduce can reach.
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "ici_bytes_per_s_per_link": 45e9,
        "source": "cloud.google.com/tpu/docs/v5e; ICI: jax-ml.github.io/"
                  "scaling-book",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]     # the other spelling of the kind


def lookup(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmark/lib/peaks.py; "
            "add its published peaks with their source before measuring on "
            "it") from None
