"""repro — JSDoop (IEEE Access 2019) reproduced as a JAX/TPU training framework.

Layers:
- ``repro.core``        — faithful JSDoop runtime (queues, DataServer, volunteers,
                          discrete-event simulator).
- ``repro.models``      — pure-JAX model zoo (10 assigned architectures + the
                          paper's LSTM).
- ``repro.optim``       — RMSprop/SGD/Adam + gradient compression.
- ``repro.distributed`` — pjit/shard_map production mapping of the JSDoop schedule.
- ``repro.kernels``     — Pallas TPU kernels (compiled; interpret on request).
- ``repro.launch``      — mesh / dry-run / train / serve entry points.
"""
__version__ = "1.0.0"
