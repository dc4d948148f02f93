"""Train ResNet-18 on synthetic CIFAR-shaped data (BASELINE config-0 shape).

Run: python examples/train_resnet_cifar.py [--steps 50]
"""
import argparse

import numpy as np

import paddle_tpu as paddle


def main(steps=50, batch=32):
    model = paddle.vision.models.resnet18(num_classes=10)
    opt = paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                    parameters=model.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    rng = np.random.default_rng(0)
    for step in range(steps):
        x = paddle.to_tensor(
            rng.standard_normal((batch, 3, 32, 32)).astype("float32"))
        y = paddle.to_tensor(rng.integers(0, 10, batch))
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        if step % 10 == 0:
            print(f"step {step}: loss {float(loss.numpy()):.4f}")
    return model


if __name__ == "__main__":
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=50)
    main(p.parse_args().steps)
