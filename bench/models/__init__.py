"""Model modules: what one configuration's model brings to the harness.

A configuration file (``bench/configs/<name>.json``) names its module by
its ``"model"`` key; ``bench/run.py`` loads ``bench/models/<model>.py``
by path. A new model is a new module here and new data files; no
harness file changes. A module defines:

``KERNELS``
    the Pallas kernel names, as the trace shows them, under which
    ``frame_work`` counts its work (``ops.MATMUL`` among them).
``TINY``
    the configuration keys a CPU test overrides to run the cell at a
    tiny size (widths, depth, image size; the patch count follows from
    ``img_size // patch``).
``arch_config(cfg)``
    the program's ``ArchConfig`` for the configuration.
``init_params(key, cfg)``
    the float32 weight tree in the program's layout, MGNet's included
    (``reference.init_mgnet``).
``classify(params, images, score_images, keep, cfg)``
    the plain float32 logits of ``images`` whose kept patches the region
    scores of ``score_images`` choose (``reference.mgnet_scores``,
    ``reference.top_patches``).
``frame_work(cfg, frames, scored, flushes)``
    the ``ops.Work`` that ``frames`` served frames need, of which
    ``scored`` were scored by MGNet, encoded in ``flushes`` =
    [(kept patches, real frames)] launches.

The shared pieces are ``bench/reference.py``'s and ``bench/ops.py``'s;
a module imports them and copies none.
"""
