import sys, glob
from jax.profiler import ProfileData
p = sorted(glob.glob(sys.argv[1] + '/plugins/profile/*/*.xplane.pb'))[-1]
pd = ProfileData.from_file(p)
for pl in pd.planes:
    print("PLANE", pl.name)
    for ln in pl.lines:
        evs = list(ln.events)
        print("  LINE", repr(ln.name), len(evs))
        for e in evs[:5]:
            print("     ", e.name[:80], e.start_ns, e.duration_ns)
