"""Operations and bytes of one ``flash_attention`` call, from the shapes
of its trace event: result (B, Hq, Sq, d), operands (lengths, q, k, v)
with k and v of shape (B, Hkv, Sk, d). The prefill is causal with
Sq == Sk, and every query head runs (head width is a mask applied after
the kernel)."""
from chipbench.trace import hbm_bytes


def cost(shapes):
    """(FLOPs, bytes): QK^T and PV over the live causal triangle; the
    tensors the event keeps in HBM, each moved once."""
    (_, (b, hq, sq, d), _), k = shapes[0], shapes[3][1]
    pairs = sq * (sq + 1) // 2 if sq == k[2] else sq * k[2]
    return float(4 * b * hq * d * pairs), hbm_bytes(shapes)
