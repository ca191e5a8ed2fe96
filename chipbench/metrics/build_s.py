"""Host-clock seconds of the index build (k-means, lists, PQ), ending in
block_until_ready."""


def read(obs):
    return obs.build_s
