"""ml_recipe_tpu — TPU-native distributed QA fine-tuning framework.

A ground-up JAX/XLA/pjit/Pallas re-design of the capability surface of
neuro-inc/ml-recipe-distributed-pytorch (multi-host data-parallel BERT/RoBERTa
question-answering fine-tuning on the TF2.0-QA / Natural Questions task):

- SPMD training over a `jax.sharding.Mesh` (data/model/sequence axes) instead of
  process-per-GPU DDP + NCCL.
- A single jitted train step (forward + weighted multi-head loss + grad psum +
  optimizer) with `lax.scan` micro-batching instead of Python-side grad accum.
- Native bf16 mixed precision instead of NVIDIA Apex AMP levels.
- First-party Flax BERT/RoBERTa encoder + 4-head QA model.
- Host-side async input pipeline with fixed-shape batches (XLA-friendly).
- C++ WordPiece/byte-level-BPE tokenizer replacing the Rust `tokenizers` dep.
"""

__version__ = "0.1.0"
