from .dp import (DataParallelLoader, local_batch_piece,
                 make_dp_supervised_step,
                 make_dp_unsupervised_step, make_mesh,
                 replicate, shard_stacked, stack_batches)
from .dist_data import (DistDataset, DistFeature, DistGraph,
                        build_dist_edge_feature, build_dist_feature,
                        build_dist_graph)
from . import multihost
from .dist_hetero import (DistHeteroDataset, DistHeteroLinkNeighborLoader,
                          DistHeteroNeighborLoader,
                          DistHeteroNeighborSampler)
from .fused import (FusedDistEpoch, FusedDistLinkEpoch,
                    FusedDistTreeEpoch)
from .dist_sampler import (DistLinkNeighborLoader, DistLinkNeighborSampler,
                           DistNeighborLoader, DistNeighborSampler,
                           DistRandomWalker,
                           DistSubGraphLoader, DistSubGraphSampler,
                           bucket_by_owner, dist_edge_exists, dist_gather,
                           dist_sample_negative)
from .exchange import (ExchangeSpec, capacity_spec,
                       mesh_factors, plan_exchange, resolve_layout,
                       simulate_assignment)
