"""PyTorch front-end synthetic benchmark — the reference's canonical
measurement protocol (``examples/pytorch_synthetic_benchmark.py:24-110``):
init → wrap optimizer → broadcast state → warmup → timed iterations →
img/sec mean ± 1.96σ. The model is a compact handwritten residual CNN
(torchvision is not part of the TPU image); swap in any ``nn.Module``.

The interesting path being measured here is the framework's torch engine:
per-parameter hooks fire async named allreduces during ``backward()``, the
engine fuses them within each cycle, and ``opt.step()`` synchronizes — on
multi-process runs the bytes ride the negotiated data plane (XLA device
collectives or the host exchange).

Run: python examples/pytorch_synthetic_benchmark.py --num-iters 3
     python -m horovod_tpu.runner -np 2 --host-data-plane \
         python examples/pytorch_synthetic_benchmark.py
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import torch
import torch.nn.functional as F

import horovod_tpu as hvd
import horovod_tpu.torch as hvd_torch


class ResidualBlock(torch.nn.Module):
    def __init__(self, channels: int) -> None:
        super().__init__()
        self.conv1 = torch.nn.Conv2d(channels, channels, 3, padding=1,
                                     bias=False)
        self.bn1 = torch.nn.BatchNorm2d(channels)
        self.conv2 = torch.nn.Conv2d(channels, channels, 3, padding=1,
                                     bias=False)
        self.bn2 = torch.nn.BatchNorm2d(channels)

    def forward(self, x):
        h = F.relu(self.bn1(self.conv1(x)))
        return F.relu(x + self.bn2(self.conv2(h)))


class SmallResNet(torch.nn.Module):
    """Stem + residual stages + classifier; ~ResNet-18-shaped but sized for
    CPU benchmarking (the reference benches torchvision resnet50 on GPUs)."""

    def __init__(self, num_classes: int = 1000, width: int = 32,
                 blocks_per_stage: int = 2) -> None:
        super().__init__()
        self.stem = torch.nn.Conv2d(3, width, 7, stride=2, padding=3,
                                    bias=False)
        stages = []
        channels = width
        for stage in range(3):
            if stage:
                stages.append(torch.nn.Conv2d(channels, channels * 2, 1,
                                              stride=2, bias=False))
                channels *= 2
            stages.extend(ResidualBlock(channels)
                          for _ in range(blocks_per_stage))
        self.stages = torch.nn.Sequential(*stages)
        self.head = torch.nn.Linear(channels, num_classes)

    def forward(self, x):
        x = F.max_pool2d(F.relu(self.stem(x)), 3, stride=2, padding=1)
        x = self.stages(x)
        x = x.mean(dim=(2, 3))
        return self.head(x)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--image-size", type=int, default=64,
                        help="reference uses 224; smaller default keeps the "
                             "CPU demo quick")
    parser.add_argument("--num-warmup-batches", type=int, default=2)
    parser.add_argument("--num-batches-per-iter", type=int, default=2)
    parser.add_argument("--num-iters", type=int, default=3)
    parser.add_argument("--json", action="store_true",
                        help="emit one self-describing JSON result line "
                             "(the bench.py protocol)")
    args = parser.parse_args()

    hvd.init()

    torch.manual_seed(42)
    model = SmallResNet()
    optimizer = hvd_torch.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.01 * hvd.size()),
        named_parameters=model.named_parameters())

    # Reference steps 5-6: consistent start on every rank.
    hvd_torch.broadcast_parameters(model.state_dict(), root_rank=0)
    hvd_torch.broadcast_optimizer_state(optimizer, root_rank=0)

    data = torch.randn(args.batch_size, 3, args.image_size, args.image_size)
    target = torch.randint(0, 1000, (args.batch_size,))

    def benchmark_step() -> None:
        optimizer.zero_grad()
        loss = F.cross_entropy(model(data), target)
        loss.backward()
        optimizer.step()

    def log(*a):
        if hvd.rank() == 0:
            print(*a, flush=True)

    log(f"Model: SmallResNet, batch size {args.batch_size}, "
        f"ranks: {hvd.size()}")
    for _ in range(args.num_warmup_batches):
        benchmark_step()

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            benchmark_step()
        dt = time.perf_counter() - t0
        rate = args.batch_size * args.num_batches_per_iter / dt
        img_secs.append(rate)
        log(f"Iter #{i}: {rate:.1f} img/sec per rank")

    mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
    log(f"Img/sec per rank: {mean:.1f} +- {conf:.1f}")
    log(f"Total img/sec on {hvd.size()} rank(s): "
        f"{mean * hvd.size():.1f} +- {conf * hvd.size():.1f}")
    if args.json and hvd.rank() == 0:
        # Same self-describing result line as bench.py; model compute is
        # torch-CPU (torch has no TPU backend in this image) — what the
        # entry measures is the eager hook→engine→data-plane path, so the
        # plane is stamped in.
        import json

        from horovod_tpu.core.provenance import git_head_sha

        sha = git_head_sha(os.path.dirname(os.path.abspath(__file__)))
        print(json.dumps({
            "metric": "torch_synthetic_train_images_per_sec_per_rank",
            "value": round(float(mean), 2),
            "unit": "img/s",
            "vs_baseline": None,
            "front_end": "torch",
            "data_plane": os.environ.get("HOROVOD_DATA_PLANE", "auto"),
            "batch_size": args.batch_size,
            "image_size": args.image_size,
            "n_ranks": hvd.size(),
            "git_sha": sha,
        }), flush=True)
    hvd.shutdown()


if __name__ == "__main__":
    main()
