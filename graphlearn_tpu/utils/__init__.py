from .common import (format_hetero_sampler_output,
                     merge_hetero_sampler_output)
from .mixin import CastMixin
from .padding import (INVALID_ID, bucket_size, max_sampled_edges,
                      max_sampled_nodes, next_power_of_two, pad_1d, round_up)
from .profiling import (LAYERS, Metrics, capture, layer_scope, metrics,
                        start_trace, step_annotation, stop_trace)
from .tensor import convert_to_array, id2idx, to_device, to_host


def __getattr__(name):
  # checkpoint symbols are lazy: importing the module can pull orbax
  # (~4s), which every process importing the library would otherwise
  # pay — including each mp sampling producer subprocess.
  if name in ('Checkpointer', 'CheckpointMismatchError',
              'SnapshotManager'):
    from . import checkpoint
    return getattr(checkpoint, name)
  raise AttributeError(f'module {__name__!r} has no attribute {name!r}')
from .topo import (coo_to_csc, coo_to_csr, csr_to_coo, degrees_from_indptr,
                   ptr2ind)
from .units import format_size, parse_size
