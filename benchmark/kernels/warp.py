"""Operations and bytes of the bilinear backward warp, from its shapes.

Forward, per output element (B*H*W*C): a blend of four neighbours with
weights (1-wx)(1-wy), (1-wx)wy, wx(1-wy), wx*wy: four multiplies and three
adds, and per pixel the four weights from the two fractions (floor, two
subtractions, two complements, four products: 10). Bytes: the image read
once, the flow read once, the output written once, all float32.

Flow gradient, per pixel: d out/d wx and d out/d wy are each a difference
of two blends (per channel 6 multiply/adds each), multiplied by the
cotangent and summed over channels (2 per channel each): 16 per element,
plus the 10 per pixel for the weights. Bytes: image, flow and cotangent
read once, the two-channel gradient written once.
"""


def forward(b: int, h: int, w: int, c: int, itemsize: int = 4) -> dict:
    px = b * h * w
    return {"ops": px * c * 7 + px * 10,
            "bytes": (px * c + px * 2 + px * c) * itemsize}


def flow_grad(b: int, h: int, w: int, c: int, itemsize: int = 4) -> dict:
    px = b * h * w
    return {"ops": px * c * 16 + px * 10,
            "bytes": (px * c + px * 2 + px * c + px * 2) * itemsize}
