"""A benchmark run, as the driver makes it, that also keeps the program's
records of the named phases, whole (PR 52; ``run_with_record.py`` keeps ``superstep_delta``'s lists alone).

    python _proof/run_with_phases.py <records.jsonl> program_memory,compile benchmark/run.py --workload cdlp-g500-24 ...

``benchmark/run.py`` runs unchanged as ``__main__`` from the current directory's
checkout; ``MetricsSink.emit`` of that checkout is wrapped when its module loads,
so that each record of a named phase is also appended to the file. In eight cells
only the warm-up job has a sink; ``lcc-g500-22``'s and the pipeline's timed jobs
write theirs too."""
import importlib.abc
import importlib.util
import json
import os
import runpy
import sys

OUT, PHASES, SCRIPT = os.path.abspath(sys.argv[1]), sys.argv[2].split(","), sys.argv[3]


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
                if phase in PHASES:
                    with open(OUT, "a") as f:
                        f.write(json.dumps(dict(kv, phase=phase), default=str) + "\n")
                return emit(sink, phase, _span=_span, **kv)

            module.MetricsSink.emit = tee

        spec.loader.exec_module = exec_module
        return spec


sys.meta_path.insert(0, _Tee())
sys.argv = sys.argv[3:]
sys.path[0] = os.path.dirname(os.path.abspath(SCRIPT))
runpy.run_path(SCRIPT, run_name="__main__")
