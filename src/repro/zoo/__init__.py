"""Mobile model zoo: the paper's nine networks + measured profile tables,
and the published YOLOv8n and FastSAM-s."""
from .executable import DagExecutable
from .mobile import ExecutableMobileModel, all_cost_graphs, executable_zoo, make_cost_graph
from .profiles import (
    MODEL_NAMES,
    MODEL_SPECS,
    TABLE4_RATIO,
    best_processor_times_s,
    paper_profile_tables,
)
from .yolov8 import YoloV8

__all__ = [k for k in dir() if not k.startswith("_")]
