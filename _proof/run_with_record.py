"""A benchmark run, as the driver makes it, that also keeps the program's
``superstep_delta`` records (ISSUE 43's review: the mesh cell's driver prints none).

    python _proof/run_with_record.py <records.jsonl> benchmark/run.py --workload cdlp-g500-25-x4 ...

``benchmark/run.py`` runs unchanged as ``__main__`` from the current directory's
checkout; ``MetricsSink.emit`` of that checkout is wrapped when its module loads,
so that each ``superstep_delta`` record is also appended to the file. Only the
warm-up job has a sink: the timed jobs emit nothing and run what they ran."""
import importlib.abc
import importlib.util
import json
import os
import runpy
import sys

OUT, SCRIPT = os.path.abspath(sys.argv[1]), sys.argv[2]
KEEP = ("branch", "reduce", "dirty_rows", "dirty_slots", "changed_vertices",
        "changed_messages", "seconds", "shards", "scan")


class _Tee(importlib.abc.MetaPathFinder):
    name = "graphmine_tpu.pipeline.metrics"

    def find_spec(self, name, path, target=None):
        if name != self.name:
            return None
        sys.meta_path.remove(self)
        spec = importlib.util.find_spec(name)
        run = spec.loader.exec_module

        def exec_module(module):
            run(module)
            emit = module.MetricsSink.emit

            def tee(sink, phase, _span=None, **kv):
                if phase == "superstep_delta":
                    with open(OUT, "a") as f:
                        f.write(json.dumps({k: kv[k] for k in KEEP if k in kv}, default=str) + "\n")
                return emit(sink, phase, _span=_span, **kv)

            module.MetricsSink.emit = tee

        spec.loader.exec_module = exec_module
        return spec


sys.meta_path.insert(0, _Tee())
sys.argv = sys.argv[2:]
sys.path[0] = os.path.dirname(os.path.abspath(SCRIPT))
runpy.run_path(SCRIPT, run_name="__main__")
