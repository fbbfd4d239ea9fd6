"""The on-chip benchmark of the pHNSW vector-search service.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once. Cells, deployments, traffic mixes
and per-layer metric readers are files found by name (see ``spec``).
"""
