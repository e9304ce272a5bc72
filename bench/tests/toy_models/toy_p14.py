"""Opto-ViT's block for a configuration at patch 14: every function is
``opto_vit``'s; only the Tiny size differs (56 px, 16 patches)."""

from bench.models.opto_vit import (KERNELS, arch_config,  # noqa: F401
                                   classify, frame_work, init_params)

TINY = {"n_layers": 2, "d_model": 192, "n_heads": 3, "d_ff": 768,
        "img_size": 56}
