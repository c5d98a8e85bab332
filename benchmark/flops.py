"""Operations a training step requires, from layer shapes alone.

``train_flops_per_image`` in a configuration's file is worked out once by
these functions from the plain reference's convolution and dense shapes:
2 x multiply-accumulates for the forward product, and twice that again for
the two backward products (input gradient and weight gradient) -- x3 in
all.  Nothing else is counted (no normalisation, activation, loss or
optimizer), and nothing is read from a compiled program, so the count is
the same whatever implements the step.

    python -m benchmark.flops            # prints the table the files hold
"""

from __future__ import annotations


def conv_macs(out_hw: int, k: int, cin: int, cout: int) -> int:
    return out_hw * out_hw * k * k * cin * cout


def resnet50_forward_macs(image: int = 224, classes: int = 1000,
                          stages=(3, 4, 6, 3)) -> int:
    size = image // 2
    macs = conv_macs(size, 7, 3, 64)
    size //= 2  # max pool
    cin = 64
    for stage, blocks in enumerate(stages):
        width = 64 * 2 ** stage
        for i in range(blocks):
            stride = 2 if stage > 0 and i == 0 else 1
            macs += conv_macs(size, 1, cin, width)        # 1x1 at the input size
            size //= stride
            macs += conv_macs(size, 3, width, width)      # the strided 3x3
            macs += conv_macs(size, 1, width, 4 * width)
            if i == 0:
                macs += conv_macs(size, 1, cin, 4 * width)  # projection
            cin = 4 * width
    return macs + cin * classes


def yolov3_forward_macs(image: int = 416, classes: int = 80,
                        blocks=(1, 2, 8, 8, 4)) -> int:
    size = image
    macs = conv_macs(size, 3, 3, 32)
    cin = 32
    for n in blocks:  # Darknet-53: a strided 3x3, then n residual pairs
        size //= 2
        macs += conv_macs(size, 3, cin, 2 * cin)
        cin *= 2
        macs += n * (conv_macs(size, 1, cin, cin // 2)
                     + conv_macs(size, 3, cin // 2, cin))
    out = 3 * (5 + classes)

    def neck(size, cin, f):  # 1-3-1-3-1, then the head's 3x3 and 1x1
        m = conv_macs(size, 1, cin, f) + conv_macs(size, 3, f, 2 * f)
        m += 2 * conv_macs(size, 1, 2 * f, f) + conv_macs(size, 3, f, 2 * f)
        return m + conv_macs(size, 3, f, 2 * f) + conv_macs(size, 1, 2 * f, out)

    macs += neck(size, 1024, 512)
    macs += conv_macs(size, 1, 512, 256) + neck(2 * size, 256 + 512, 256)
    macs += conv_macs(2 * size, 1, 256, 128) + neck(4 * size, 128 + 256, 128)
    return macs


def lenet5_forward_macs() -> int:
    return (conv_macs(28, 5, 1, 6) + conv_macs(10, 5, 6, 16)
            + conv_macs(1, 5, 16, 120) + 120 * 84 + 84 * 10)


def train_flops(forward_macs: int) -> int:
    return 3 * 2 * forward_macs


if __name__ == "__main__":
    for name, macs in (("resnet50", resnet50_forward_macs()),
                       ("yolov3-416", yolov3_forward_macs()),
                       ("lenet5", lenet5_forward_macs())):
        print(f"{name}: forward {macs} MAC, train_flops_per_image "
              f"{train_flops(macs)}")
