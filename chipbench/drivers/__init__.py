"""Traffic drivers, one module each, found by the name a
``traffic/<mix>.json`` file gives under ``generator`` (see
``chipbench.common.Cell``)."""
