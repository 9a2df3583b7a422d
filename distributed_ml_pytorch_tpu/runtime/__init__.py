from distributed_ml_pytorch_tpu.runtime.mesh import (
    initialize_distributed,
    data_mesh,
    make_mesh,
    force_cpu_devices,
    local_device_count,
    process_rank,
    world_size,
)

__all__ = [
    "initialize_distributed",
    "data_mesh",
    "make_mesh",
    "force_cpu_devices",
    "local_device_count",
    "process_rank",
    "world_size",
]
