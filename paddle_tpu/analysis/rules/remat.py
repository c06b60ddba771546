"""Rule ``involuntary-remat``: SPMD partitioner full-rematerialization
resharding — the replicate-then-repartition pattern that moves a tensor's
FULL bytes over the wire (and doubles its HBM residency) because the
compiler could not find an efficient path between two sharding layouts.

Two detection layers:

1. **Partitioner diagnostics** (primary).  ``spmd_partitioner.cc`` warns
   per occurrence on compile-time stderr; both message dialects are
   parsed (older XLA: "cannot go from sharding {X} to {Y} efficiently";
   newer: "was not able to go from sharding {X} to {Y} without doing a
   full rematerialization").  Each warning names the HLO op, its type and
   the two shardings; occurrences with the same (op kind, shape, source
   location) fold into one finding with a count.

2. **HLO reshard pattern** (fallback when no diagnostics were captured,
   e.g. linting an already-compiled executable).  The materialized form
   of the last-resort reshard is an ``all-gather`` to the full tensor
   immediately re-partitioned by a ``dynamic-slice`` — matched textually
   in the optimized module.

Pricing: the last-resort reshard replicates the tensor (ring all-gather:
``(n-1)/n × full_bytes`` per chip) and then slices locally (free), so
each occurrence is priced at ``full_bytes × (n-1)/n`` wire bytes, with
``n`` the participant count read off the sharding's device assignment —
the ring-cost model of ``telemetry.ring_wire_bytes``.
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

from ..findings import Finding, Severity
from ..program import ProgramArtifacts, shape_bytes
from . import rule

__all__ = ["parse_partitioner_diagnostics"]

# both spmd_partitioner dialects: "cannot go from sharding {X} to {Y}
# efficiently for HLO operation %op" (older XLA, W-level) and "was not
# able to go from sharding {X} to {Y} without doing a full
# rematerialization of the tensor for HLO operation: %op" (newer, E-level)
_REMAT_RE = re.compile(
    r"Involuntary full rematerialization\..*?go from sharding "
    r"\{(?P<from>[^}]*)\} to \{(?P<to>[^}]*)\}.*?"
    r"for HLO operation:?\s+%(?P<op>[\w.\-]+)\s*=\s*"
    r"(?P<dtype>\w+)\[(?P<dims>[\d,]*)\]")

_SRC_RE = re.compile(r'source_file="([^"]+)"(?:\s+source_line=(\d+))?')
_OP_NAME_RE = re.compile(r'op_name="([^"]+)"')
_DEVICES_RE = re.compile(r"devices=\[([\d,]+)\]")


def _participants(sharding: str, fallback: int) -> int:
    """Number of distinct SHARDS in an HLO sharding string — the ring
    size a replicate-then-repartition gather runs over.  The tile-dims
    product counts every device; with ``last_tile_dim_replicate`` the
    last tile dim is replication, not sharding, so it divides out
    (``devices=[4,1,2] ... last_tile_dim_replicate`` = 4 shards x2
    replicas, and the gather moves (4-1)/4 of the tensor, not 7/8)."""
    m = _DEVICES_RE.search(sharding)
    if not m:
        return max(1, fallback)
    dims = [int(d) for d in m.group(1).split(",") if d.strip()]
    n = 1
    for d in dims:
        n *= d
    if "last_tile_dim_replicate" in sharding and dims:
        n //= max(1, dims[-1])
    return max(1, n)


def _short_source(path: str) -> str:
    # stable across checkouts: strip everything before the package root
    for anchor in ("paddle_tpu/", "site-packages/"):
        i = path.find(anchor)
        if i >= 0:
            return path[i:]
    return path


def parse_partitioner_diagnostics(text: str, n_devices: int = 1) -> List[dict]:
    """Parse captured compile stderr into one record per remat warning:
    ``{op, op_kind, dtype, dims, from, to, source, op_name, full_bytes,
    wire_bytes}``."""
    out = []
    for line in text.splitlines():
        m = _REMAT_RE.search(line)
        if m is None:
            continue
        d = m.groupdict()
        srcm = _SRC_RE.search(line)
        source = None
        if srcm:
            source = _short_source(srcm.group(1))
            if srcm.group(2):
                source += f":{srcm.group(2)}"
        opn = _OP_NAME_RE.search(line)
        full = shape_bytes(d["dtype"], d["dims"])
        n = _participants(d["from"], n_devices)
        out.append({
            "op": d["op"],
            "op_kind": re.sub(r"[.\d]+$", "", d["op"]),
            "dtype": d["dtype"], "dims": d["dims"],
            "from": d["from"], "to": d["to"],
            "source": source,
            "op_name": opn.group(1) if opn else None,
            "full_bytes": full,
            "wire_bytes": int(full * (n - 1) / max(1, n)),
            "participants": n,
        })
    return out


_AG_DEF_RE = re.compile(
    r"%([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\][^=]*?\ball-gather\(")


@rule("involuntary-remat")
def check_involuntary_remat(art: ProgramArtifacts,
                            config: dict) -> List[Finding]:
    findings: List[Finding] = []
    records = parse_partitioner_diagnostics(art.diagnostics or "",
                                            art.n_devices)
    grouped: Dict[Tuple, dict] = {}
    for r in records:
        key = (r["op_kind"], r["dtype"], r["dims"], r["source"])
        g = grouped.setdefault(key, {**r, "count": 0, "total_wire": 0})
        g["count"] += 1
        g["total_wire"] += r["wire_bytes"]
    for (op_kind, dtype, dims, source), g in grouped.items():
        findings.append(Finding(
            rule="involuntary-remat",
            severity=Severity.ERROR,
            subject=f"{op_kind} {dtype}[{dims}]",
            message=(
                f"SPMD partitioner fell back to full rematerialization "
                f"resharding {g['from']!s} -> {g['to']!s} "
                f"(replicate-then-repartition: unpriced wire + HBM)"),
            cost_bytes=g["total_wire"],
            fix=("make the producing/consuming sharding specs agree "
                 "(constrain the tensor once, at the layout both sides "
                 "accept) or add an explicit reshard on the smaller form"),
            source=source,
            count=g["count"],
            context={"from": g["from"], "to": g["to"],
                     "participants": g["participants"],
                     "op_name": g.get("op_name"),
                     "signature_extra": f"{g['from']}->{g['to']}"},
        ))
    if findings or not art.hlo_text:
        return findings

    # fallback: the materialized replicate-then-repartition pattern in the
    # optimized HLO (all-gather to full immediately re-sliced)
    text = art.hlo_text
    for m in _AG_DEF_RE.finditer(text):
        name, dtype, dims = m.groups()
        if re.search(r"dynamic-slice\([^)]*%" + re.escape(name) + r"\b",
                     text):
            full = shape_bytes(dtype, dims)
            n = max(1, art.n_devices)
            findings.append(Finding(
                rule="involuntary-remat",
                severity=Severity.ERROR,
                subject=f"all-gather->dynamic-slice {dtype}[{dims}]",
                message=("optimized HLO materializes a full all-gather "
                         "that is immediately re-partitioned by a "
                         "dynamic-slice — the replicate-then-repartition "
                         "reshard pattern"),
                cost_bytes=int(full * (n - 1) / n),
                fix="align the producer/consumer sharding specs",
                context={"pattern": "hlo", "instruction": name},
            ))
    return findings
