from .sharding import placement_put, snn_mesh, snn_rules, spec_for_shape
from .fault_tolerance import HeartbeatRegistry, RestartPolicy, StragglerDetector
