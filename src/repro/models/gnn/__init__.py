from repro.models.gnn.models import GNNConfig, init_gnn, gnn_apply
from repro.models.gnn.policy import BackendPolicy

__all__ = ["GNNConfig", "init_gnn", "gnn_apply", "BackendPolicy"]
