"""Core paper contribution: neuromorphic core-interface models in JAX.

- arbiter:       five arbitration architectures (HAT = the paper's), closed
                 forms + discrete-event simulation (Tables I-III, Fig. 5)
- aer:           address-event encode/decode + raster streaming
- cam:           asynchronous CAM with CSCD / feedback / speculative sense
                 (Figs. 9-11), functional search + behavioural PPA models
- event_router:  HAT-style hierarchical MoE token dispatch (beyond-paper)
- fabric:        DEPRECATED shim over `repro.interface` (the unified,
                 registry-driven core-interface API with compiled sessions);
                 not imported here, since it imports `repro.interface`,
                 whose config imports this package - import it by name
- ppa:           calibration constants shared by the models
"""

from repro.core import aer, arbiter, cam, event_router, ppa  # noqa: F401
