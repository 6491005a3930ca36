"""Operations and bytes of the multiplicative correlation, from its shapes.

corr[b,y,x,i] = mean_c f1[b,y,x,c] * f2[b,y+dy_i,x+dx_i,c] over n*n
displacements, n = 2*(max_disp//stride)+1: per output element C multiplies
and C-1 adds and one scale, so 2*C. Bytes: both feature maps read once (the
least any algorithm moves), the n*n maps written once.
"""


def forward(b: int, h: int, w: int, c: int, max_disp: int, stride: int,
            in_itemsize: int = 2, out_itemsize: int = 2) -> dict:
    n = 2 * (max_disp // stride) + 1
    out = b * h * w * n * n
    return {"ops": out * 2 * c,
            "bytes": 2 * b * h * w * c * in_itemsize + out * out_itemsize}
