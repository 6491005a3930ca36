"""Each kernel's count against a shape worked by hand."""

from benchmark.kernels import corr, roofline, warp


def test_warp_forward_counts():
    # 1x2x3 pixels, 3 channels: 6 pixels, 18 elements
    c = warp.forward(1, 2, 3, 3)
    assert c["ops"] == 18 * 7 + 6 * 10 == 186
    assert c["bytes"] == (18 + 12 + 18) * 4 == 192


def test_warp_flow_grad_counts():
    c = warp.flow_grad(1, 2, 3, 3)
    assert c["ops"] == 18 * 16 + 6 * 10 == 348
    assert c["bytes"] == (18 + 12 + 18 + 12) * 4 == 240


def test_corr_counts():
    # max displacement 2 in steps of 1: 5x5 = 25 maps; 1x2x2 pixels, 4 channels
    c = corr.forward(1, 2, 2, 4, max_disp=2, stride=1, in_itemsize=2, out_itemsize=4)
    assert c["ops"] == 4 * 25 * 2 * 4 == 800
    assert c["bytes"] == 2 * 16 * 2 + 100 * 4 == 464


def test_least_seconds_names_the_bound():
    peaks = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert roofline.least_seconds({"ops": 1000, "bytes": 10}, peaks) == (10.0, "compute")
    assert roofline.least_seconds({"ops": 10, "bytes": 1000}, peaks) == (100.0, "memory")
