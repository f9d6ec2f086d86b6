from .mesh import (make_mesh, apply_dp_sharding,  # noqa: F401
                   apply_dp_tp_sharding, apply_dp_sp_sharding,
                   apply_dp_pp_sharding, apply_dp_pp_tp_sharding,
                   apply_dp_tp_sp_sharding, apply_zero_sharding,
                   rebuild_mesh)
