"""Operations and bytes of the correlation's backward, from its shapes.

df1[b,y,x,:] = (1/C) sum_i g[b,y,x,i] f2[b,y+dy_i,x+dx_i,:] and
df2[b,y+dy_i,x+dx_i,:] += (1/C) g[b,y,x,i] f1[b,y,x,:]: per element of the
n*n maps, C multiply-adds into each of the two gradients, so 4*C
operations. Bytes: both feature maps and the cotangent read once, both
gradients written once (the least any algorithm moves). A module of its
own beside `corr.py`, whose file the PR that added this count could not
edit.
"""


def backward(b: int, h: int, w: int, c: int, max_disp: int, stride: int,
             in_itemsize: int = 2, out_itemsize: int = 2) -> dict:
    n = 2 * (max_disp // stride) + 1
    maps = b * h * w * n * n
    feats = b * h * w * c
    return {"ops": maps * 4 * c,
            "bytes": (2 * feats + maps) * in_itemsize + 2 * feats * out_itemsize}
