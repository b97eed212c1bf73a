from .conv import (GATConv, GATv2Conv, GCNConv, GINConv, SAGEConv,
                   segment_mean, segment_max)
from .basic_gnn import DGCNN, GAT, GCN, GIN, BasicGNN, GraphSAGE
from .tree import TreeSAGE, tree_level_sizes
from .hetero import (HGT, HGTConv, HeteroConv, RGAT, RGCN,
                     typed_layer_extent)
from .train import (TrainState, apply_to_batch, create_train_state,
                    make_eval_step,
                    make_supervised_step, make_unsupervised_step,
                    link_loss_from_metadata, supervised_loss,
                    triplet_link_loss, unsupervised_link_loss)
