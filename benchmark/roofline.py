"""Operations and bytes of the geofence containment, from a
configuration's logical shape, and the peaks of the chips it runs on.

The containment is the even-odd rule for B points against Z polygons of
V edges each: per (point, zone, edge), two compares and an xor for
"the edge straddles the point's latitude", x1 + (x2 - x1)(py - y1)/dy
(two subtractions, a multiply, a divide, an add), the compare of the
point's longitude with that crossing, an and and the parity xor: 11
vector operations. Its least traffic is the points (two float32 each),
the polygons' vertices (two float32 each) and one byte of result per
(point, zone). The kernel's padded tiles, its guard against horizontal
edges and any second containment (the `enter` condition's previous
points) are work beyond this, so the share cannot pass 100 % by a
change of tiling or algorithm.
"""

from typing import Dict, Tuple

OPS_PER_EDGE_TEST = 11

# Peaks by `device_kind`, with where each number comes from.
#   hbm_bytes_per_s: Google Cloud documentation, "TPU v5e": 819 GB/s.
#   vpu_ops_per_s: the vector unit of the chip's one TensorCore works on
#     8 x 128 32-bit lanes (JAX documentation, "Pallas TPU details": a
#     vector register is 8 x 128), with 4 vector ALU slots per cycle
#     ("How to Scale Your Model", Austin et al., 2025, the TPU chapter),
#     at 1.5 GHz: the published 197 TFLOP/s bf16 over 4 MXUs of
#     128 x 128 multiply-adds (Google Cloud documentation, "TPU v5e").
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "vpu_ops_per_s": 8 * 128 * 4 * (197e12 / (4 * 128 * 128 * 2)),
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def containment_ops(b: int, z: int, v: int) -> float:
    return float(OPS_PER_EDGE_TEST) * b * z * v


def containment_bytes(b: int, z: int, v: int) -> float:
    return 8.0 * b + 8.0 * z * v + 1.0 * b * z


def least_seconds(device_kind: str, b: int, z: int,
                  v: int) -> Tuple[float, str]:
    """The least time the chip could take for one containment, and the
    bound that sets it ("vpu" or "hbm")."""
    p = peaks(device_kind)
    compute = containment_ops(b, z, v) / p["vpu_ops_per_s"]
    memory = containment_bytes(b, z, v) / p["hbm_bytes_per_s"]
    return (compute, "vpu") if compute >= memory else (memory, "hbm")
