"""Hand-written TPU kernels (Pallas) for the framework's hot ops.

Import the kernel's module (``ops.flash_attention``, ``ops.fused_xent``,
``ops.paged_decode``); the package itself imports none of them, so
``ops.util`` (the dispatch rule and its mark) costs no Pallas import."""
