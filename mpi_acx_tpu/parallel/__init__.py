"""TPU data plane: mesh-based collectives, partitioned exchange, ring
attention, and the microbatch pipeline.

This package is the ICI half of the framework (the native C++ runtime in
``src/`` is the host half): the reference's CUDA/MPI primitives re-expressed
as JAX/XLA collectives over a ``jax.sharding.Mesh``, per the SURVEY.md §7.1
mapping table. Everything here is jit-compatible, static-shaped, and runs
identically on a real TPU slice and on a virtual CPU mesh.
"""

from mpi_acx_tpu.parallel.mesh import (  # noqa: F401
    make_mesh,
    mesh_from_devices,
)
from mpi_acx_tpu.parallel.collective import (  # noqa: F401
    ring_shift,
    neighbor_exchange,
    halo_exchange_1d,
    halo_exchange_2d,
    all_to_all_seq,
)
from mpi_acx_tpu.parallel.partitioned import (  # noqa: F401
    partitioned_ring_exchange,
    partitioned_pipeline,
)
from mpi_acx_tpu.parallel.ring_attention import (  # noqa: F401
    ring_attention,
    ring_attention_batched,
    ring_attention_sharded,
    blockwise_attention_reference,
)
from mpi_acx_tpu.parallel.pipeline import (  # noqa: F401
    pipeline_1f1b_loss_and_grads,
    pipeline_forward,
    pipeline_forward_interleaved,
    pipeline_loss,
)
from mpi_acx_tpu.parallel.ulysses import (  # noqa: F401
    ulysses_attention,
    ulysses_attention_sharded,
)
from mpi_acx_tpu.parallel.quantized import (  # noqa: F401
    quantized_pmean,
    quantized_psum,
    ring_psum,
)
from mpi_acx_tpu.parallel.tp_inference import (  # noqa: F401
    make_tp_generate,
    make_tp_generate_llama,
    make_tp_generate_moe,
    make_tp_speculative_generate,
    tp_param_specs,
    tp_param_specs_llama,
    tp_param_specs_moe,
    tp_shard_params,
    tp_shard_params_llama,
    tp_shard_params_moe,
)
from mpi_acx_tpu.parallel import multihost  # noqa: F401
