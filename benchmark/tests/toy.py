"""A toy copy of the benchmark's data files in a temporary root: the
real ``BENCHMARK.json`` and configuration files, traffic mixes cut to a
few images, and a table of peaks with a row for the CPU, so that the
whole of a run can be driven here.  Nothing it yields is a measurement."""

import json
import os
import shutil
import time

from benchmark import harness

TOY_TRAFFIC = {
    "jpeg_files": {"generator": "jpeg_files", "image_height": 60,
                   "image_width": 80, "jpeg_quality": 90,
                   "distinct_images": 5, "batch_size": 4,
                   "job_batches": 1.5, "directories": 2,
                   "num_partitions": 1, "warm_images": 2},
    "image_structs": {"generator": "image_structs", "distinct_images": 5,
                      "batch_size": 4, "job_batches": 3.5, "frames": 2,
                      "warm_rows": 2},
}


#: what a later PR would enter, as data alone: the same program stated at
#: float32 (PERF.md section 7), whose control is a path of the program's
#: own, over decoded image structs
F32 = {"name": "inceptionv3.f32", "source": "https://arxiv.org/abs/1512.00567",
       "file": "benchmark/configs/inceptionv3_f32.json", "reduced": [],
       "why": "the same program at float32"}
F32_STRUCTS = {"name": "inceptionv3.f32.structs", "config": "inceptionv3.f32",
               "traffic": "image_structs", "chips": 1,
               "why": "decode bypassed: packing, pipeline, the model "
                      "program and padding set the pace"}


def make_root(tmp_path, extra_traffic=None, extra_workloads=()):
    """``(root, peaks_path)``: a root that holds the benchmark's data at
    toy size, ``inceptionv3.f32.structs`` entered as a later PR would
    enter it, plus whatever a test adds the same way."""
    root = str(tmp_path / "root")
    os.makedirs(os.path.join(root, "benchmark", "traffic"))
    shutil.copytree(os.path.join(harness.BENCH_DIR, "configs"),
                    os.path.join(root, "benchmark", "configs"))
    with open(os.path.join(root, "benchmark", "configs",
                           "inceptionv3.json")) as fh:
        f32 = json.load(fh)
    # on the tests' CPU float32 is float32: the program reads 1e-6 of the
    # feature scale, its bfloat16 path a hundredth
    f32.update(name=F32["name"], compute_dtype="float32",
               limits={"feature_gap": 0.001},
               control={"kind": "program_env",
                        "env": {"SPARKDL_ZOO_COMPUTE_DTYPE": "bfloat16"}})
    with open(os.path.join(root, F32["file"]), "w") as fh:
        json.dump(f32, fh)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    extra_workloads = [F32_STRUCTS, *extra_workloads]
    bench["configs"].append(F32)
    bench["workloads"].extend(extra_workloads)
    for w in extra_workloads:
        for m in bench["per_layer"]:
            if m["name"] != "decode_ms_per_image":
                m["workloads"].append(w["name"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    for name, mix in {**TOY_TRAFFIC, **(extra_traffic or {})}.items():
        with open(os.path.join(root, "benchmark", "traffic",
                               name + ".json"), "w") as fh:
            json.dump(mix, fh)
    peaks = os.path.join(root, "peaks.json")
    with open(peaks, "w") as fh:
        json.dump({"cpu": {"bf16_flops_per_s": 1e12,
                           "source": "made up: the tests' CPU"}}, fh)
    return root, peaks


def run(root, peaks, cell_name, seed=7, seconds=0.5, control=False):
    """The rest of a run behind the look for a chip, untraced."""
    cell = harness.load_cell(root, cell_name)
    return harness.run_cell(cell, seed, seconds, False,
                            process_start=time.perf_counter(),
                            platform=None, control=control,
                            peaks_path=peaks, root=root)
