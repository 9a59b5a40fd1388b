from repro.kernels.maze_route.frontier import wavefront_distance_frontier
from repro.kernels.maze_route.ops import (INF, goal_route, goal_wavefront,
                                          pad_blocked, wavefront_distance)
from repro.kernels.maze_route.oracle import wavefront_distance_bfs
from repro.kernels.maze_route.ref import NEIGHBORS, wavefront_distance_ref

__all__ = ["INF", "NEIGHBORS", "goal_route", "goal_wavefront", "pad_blocked",
           "wavefront_distance", "wavefront_distance_bfs",
           "wavefront_distance_frontier", "wavefront_distance_ref"]
