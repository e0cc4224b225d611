"""Distribution layer: mesh context, partition rules, pipeline parallelism,
fault tolerance / elastic re-mesh."""
