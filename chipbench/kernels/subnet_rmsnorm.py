"""Operations and bytes of one ``subnet_rmsnorm`` call, from the shapes
of its trace event: result (M, d), operands (subnet id, x (M, d), the
float32 gain table (n_subnets, 1, d), of which one row is read)."""
from chipbench.trace import hbm_bytes


def cost(shapes):
    """(FLOPs, bytes): square, mean, scale and gain per element; x and the
    result where they are kept in HBM, and one gain row."""
    m, d = shapes[0][1]
    moved = hbm_bytes(shapes[:3]) + 4 * d
    return float(4 * m * d), moved
