"""repro: Seismic (SIGIR'24) as a multi-pod JAX framework.

Layers:
  core/        the paper's contribution (index build + approximate query)
  sparse/      padded-sparse vector substrate
  kernels/     Pallas TPU kernels for the scoring hot-spots
  models/      assigned architecture pool (LM transformers, GNN, recsys)
  data/        synthetic data generators + host pipeline
  train/       optimizer, train loop, grad compression
  serve/       decode + retrieval serving engines
  tune/        recall-target operating-point autotuner (TunedPolicy)
  ckpt/        sharded checkpointing with elastic re-mesh
  distributed/ mesh helpers, sharding rules, roofline math
  configs/     selectable architecture configs (--arch <id>)
  launch/      mesh.py, dryrun.py, train.py, serve.py
"""

__version__ = "0.1.0"
