"""MFIT core: the paper's multi-fidelity thermal model family.

Fidelity ladder (paper Fig. 2):
  FVMReference (golden, stands in for FEM)  ->  ThermalRCModel (seconds)
  ->  DSSModel (milliseconds)  ->  ROMModel (microsecond steps,
  node-count independent)  ->  ThermalManager (runtime DTPM).

All fidelities share the ``ThermalSimulator`` protocol and are built by
string through the registry, at two levels:

  ``build(pkg, fidelity="rc"|"fvm"|"dss"|...)``   one concrete package
  ``build_family(PackageFamily(pkg, params=...))`` a whole design space,
      evaluated as a device batch axis (``BatchedThermalSimulator``).

Matmul precision: a TPU's default rounds f32 matmul operands to bf16 (8
mantissa bits), which puts f32 temperatures ~0.1 °C off and stalls f32
CG. The models need f32 matmuls to be f32, so importing this package
sets JAX's default matmul precision to "highest" unless the process has
already chosen one. The Pallas kernels ask for it explicitly.
"""
import jax

from ..distribution.family_exec import FamilyExecutor
from .assembly import NumericAssembly, SymbolicNetwork, symbolic_network
from .baselines import BASELINES, hotspot_like, pact_like, threedice_like
from .calibrate import (default_cap_multipliers, multipliers_by_layer_name,
                        tune_capacitance)
from .dss import (ContinuousSS, DSSFamilyModel, DSSModel, EighZOH,
                  continuous_ss, discretize_css, discretize_rc,
                  spectral_radius, zoh_discretize)
from .dtpm import DTPMState, ThermalManager
from .family import FamilyParam, PackageFamily, TopologyError
from .fidelity import (SOLVER_CROSSOVER_NODES, BatchedThermalSimulator,
                       ThermalSimulator, available_family_fidelities,
                       available_fidelities, build, build_family, cache_key,
                       register_family_fidelity, register_fidelity,
                       resolve_solver, simulate_batch_via_vmap)
from .fvm_ref import (FVMFamilyModel, FVMReference, VoxelModel, voxelize)
from .geometry import (Block, Layer, NodeGrid, Package, chiplet_tags,
                       content_digest, content_token, discretize,
                       make_2p5d_package, make_3d_package,
                       make_tpu_tray_package, package_from_name)
from .materials import MATERIALS, HeatsinkSpec, Material
from .optimize import OptResult, minimize_multistart, optimize_family
from .power import V5E, HardwareSpec, StepCost, chip_power
from .rc_model import (RCFamilyModel, RCNetwork, ThermalRCModel,
                       build_model, build_network, observation_matrix)
from .rom import (ROMFamilyModel, ROMModel, build_rom, krylov_basis,
                  project_network)
from .router import (CostModel, ErrorCertifier, RoutedAnswer,
                     RoutedFamilySimulator, RoutedThermalSimulator)
from .workloads import ALL_WORKLOADS, P2P5D, P3D, PowerSpec, get_workload

__all__ = [
    "FamilyExecutor",
    "NumericAssembly", "SymbolicNetwork", "symbolic_network",
    "BASELINES", "hotspot_like", "pact_like", "threedice_like",
    "default_cap_multipliers", "multipliers_by_layer_name",
    "tune_capacitance",
    "ContinuousSS", "DSSFamilyModel", "DSSModel", "EighZOH",
    "continuous_ss", "discretize_css", "discretize_rc",
    "spectral_radius", "zoh_discretize",
    "DTPMState", "ThermalManager",
    "FamilyParam", "PackageFamily", "TopologyError",
    "SOLVER_CROSSOVER_NODES", "BatchedThermalSimulator",
    "ThermalSimulator",
    "available_family_fidelities", "available_fidelities",
    "build", "build_family", "cache_key", "register_family_fidelity",
    "register_fidelity", "resolve_solver", "simulate_batch_via_vmap",
    "FVMFamilyModel", "FVMReference", "VoxelModel", "voxelize",
    "Block", "Layer", "NodeGrid", "Package", "chiplet_tags",
    "content_digest", "content_token", "discretize",
    "make_2p5d_package", "make_3d_package", "make_tpu_tray_package",
    "package_from_name",
    "MATERIALS", "HeatsinkSpec", "Material",
    "OptResult", "minimize_multistart", "optimize_family",
    "V5E", "HardwareSpec", "StepCost", "chip_power",
    "RCFamilyModel", "RCNetwork", "ThermalRCModel", "build_model",
    "build_network", "observation_matrix",
    "ROMFamilyModel", "ROMModel", "build_rom", "krylov_basis",
    "project_network",
    "CostModel", "ErrorCertifier", "RoutedAnswer",
    "RoutedFamilySimulator", "RoutedThermalSimulator",
    "ALL_WORKLOADS", "P2P5D", "P3D", "PowerSpec", "get_workload",
]

if jax.config.jax_default_matmul_precision is None:
    jax.config.update("jax_default_matmul_precision", "highest")
