"""The paper's contribution: virtual DD + distributed DP inference."""
from .domain import (VirtualGrid, uniform_grid, balanced_planes, factor_grid,  # noqa: F401
                     select_local, select_ghosts, partition_costs, atom_costs,
                     bin_atoms, select_local_cells, select_ghosts_cells,
                     interior_fraction_estimate)
from .ddinfer import (DDConfig, DDState, suggest_config,  # noqa: F401
                      make_distributed_force_fn, make_assembly_fn,
                      make_evaluation_fn, make_displacement_check_fn,
                      make_batched_force_fn, make_batched_assembly_fn,
                      make_batched_evaluation_fn, make_batched_check_fn,
                      single_domain_forces, single_domain_state,
                      single_domain_forces_nlist,
                      single_domain_forces_batched,
                      masked_neighbor_list, make_padded_batch_fn)
from .pipeline import ForcePipeline, Stage  # noqa: F401
from .nnpot import DeepmdForceProvider, UnitConversion  # noqa: F401
from ..backend import (ForceBackend, ForceRequest, ForceResult,  # noqa: F401
                       StatefulForceBackend)
