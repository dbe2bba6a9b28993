"""The benchmark's training data: a seeded pool of uint8 images in memory.

`SyntheticDataset.load` draws `size*size*3` random bytes per call on the
host, so at several thousand images a second the random-number generator
would set the pace of the input pipeline. The pool draws its bytes once,
from the seed, and `load(i)` hands out `pool[i mod n]`: the host work left
in a train cell is the pipeline's own (batch assembly, transfer, augment
dispatch). `__len__` is ImageNet-1k's, so `steps_per_epoch` and the
schedules are the recipe's. Same protocol as `SyntheticDataset`
(`__len__`, `load(index, decode_size=None) -> (uint8 HWC, label)`,
`num_classes`), and nothing else.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

IMAGENET_1K_TRAIN = 1_281_167


class PoolDataset:
    def __init__(
        self,
        seed: int,
        pool_size: int = 2048,
        image_size: int = 224,
        num_examples: int = IMAGENET_1K_TRAIN,
        num_classes: int = 1000,
    ):
        self.num_examples = int(num_examples)
        self.image_size = int(image_size)
        self.num_classes = int(num_classes)
        rng = np.random.default_rng(int(seed))
        self._pool = rng.integers(
            0, 256, (int(pool_size), self.image_size, self.image_size, 3), dtype=np.uint8
        )

    def __len__(self) -> int:
        return self.num_examples

    def load(self, index: int, decode_size: Optional[int] = None) -> tuple[np.ndarray, int]:
        if decode_size not in (None, self.image_size):
            raise ValueError(
                f"the pool holds {self.image_size}px images; asked for {decode_size}"
            )
        index = int(index)
        return self._pool[index % len(self._pool)], index % self.num_classes
