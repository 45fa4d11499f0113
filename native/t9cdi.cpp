// t9cdi — TPU Container Device Interface spec generator.
//
// Reference analogue: the forked nvidia-container-toolkit the reference
// drives for CDI spec generation + sanitization
// (pkg/worker/nvidia.go:92-203, docker/Dockerfile.worker:135-153). TPU
// hosts have no nvidia-ctk equivalent, so tpu9 ships its own: enumerate
// the host's TPU device nodes (/dev/accel*, /dev/vfio/*), locate
// libtpu.so, and emit a CDI v0.6.0 JSON spec that any CDI-aware runtime
// (containerd, CRI-O, podman, runc via spec injection) can use to hand
// chips to containers — the k8s-native deployment path for tpu9 workers.
//
// Devices emitted:
//   tpu9.dev/accel=<N>   one per chip (device node + env)
//   tpu9.dev/accel=all   every chip + libtpu mount + topology env
//
// Usage:
//   t9cdi [--dev-root DIR] [--libtpu PATH] [--out FILE]
//
// --dev-root substitutes the /dev prefix (tests enumerate a fake tree);
// default output is stdout (operators typically redirect to
// /etc/cdi/tpu9.json).

#include <algorithm>
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

#include <dirent.h>
#include <sys/stat.h>
#include <unistd.h>

namespace {

std::vector<std::string> list_dir(const std::string& dir) {
  std::vector<std::string> out;
  DIR* d = opendir(dir.c_str());
  if (d == nullptr) return out;
  struct dirent* e;
  while ((e = readdir(d)) != nullptr) {
    std::string name = e->d_name;
    if (name != "." && name != "..") out.push_back(name);
  }
  closedir(d);
  std::sort(out.begin(), out.end());
  return out;
}

// chips-per-process bounds for common per-host chip counts — MUST match
// tpu9/worker/tpu_manager.py::_bounds_for (the worker-injected contract;
// t9cdi exists for k8s/containerd hosts where the Python worker is not
// the one mounting devices, but the env the container sees must agree)
std::string bounds_for(size_t chips) {
  switch (chips) {
    case 1: return "1,1,1";
    case 2: return "1,2,1";
    case 4: return "2,2,1";
    case 8: return "2,4,1";
    default: return std::to_string(chips) + ",1,1";
  }
}

bool exists(const std::string& p) {
  struct stat st;
  return stat(p.c_str(), &st) == 0;
}

std::string json_escape(const std::string& s) {
  std::string out;
  for (char c : s) {
    if (c == '"' || c == '\\') out += '\\';
    out += c;
  }
  return out;
}

struct Ctx {
  std::string dev_root = "/dev";
  std::string libtpu;
  // (chip_id, device_path): chip ids come from the node's numeric suffix,
  // NOT the enumeration index — a host with a failed chip (accel0+accel2)
  // must map TPU_VISIBLE_CHIPS to the right nodes
  std::vector<std::pair<int, std::string>> chips;
  std::vector<std::string> vfio;       // vfio group paths
};

void emit_device_node(std::string& out, const std::string& path,
                      bool last) {
  out += "        {\"path\": \"" + json_escape(path) + "\"}";
  out += last ? "\n" : ",\n";
}

std::string emit(const Ctx& ctx) {
  std::string out;
  out += "{\n";
  out += "  \"cdiVersion\": \"0.6.0\",\n";
  out += "  \"kind\": \"tpu9.dev/accel\",\n";
  out += "  \"devices\": [\n";

  // one CDI device per chip (named by the chip's real id)
  for (auto& [chip_id, path] : ctx.chips) {
    out += "    {\n";
    out += "      \"name\": \"" + std::to_string(chip_id) + "\",\n";
    out += "      \"containerEdits\": {\n";
    out += "        \"deviceNodes\": [\n";
    out += "          {\"path\": \"" + json_escape(path) + "\"}\n";
    out += "        ],\n";
    out += "        \"env\": [\n";
    out += "          \"TPU_VISIBLE_CHIPS=" + std::to_string(chip_id)
           + "\",\n";
    out += "          \"TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1\",\n";
    out += "          \"TPU_PROCESS_BOUNDS=1,1,1\",\n";
    out += "          \"TPU_SKIP_MDS_QUERY=1\",\n";
    out += "          \"JAX_PLATFORMS=tpu\"\n";
    out += "        ]\n";
    out += "      }\n";
    out += "    },\n";
  }

  // "all": the whole host slice (the common serving shape)
  out += "    {\n";
  out += "      \"name\": \"all\",\n";
  out += "      \"containerEdits\": {\n";
  out += "        \"deviceNodes\": [\n";
  {
    std::vector<std::string> nodes;
    for (auto& [id, path] : ctx.chips) nodes.push_back(path);
    nodes.insert(nodes.end(), ctx.vfio.begin(), ctx.vfio.end());
    for (size_t i = 0; i < nodes.size(); i++)
      emit_device_node(out, nodes[i], i + 1 == nodes.size());
  }
  out += "        ],\n";
  std::string chips;
  for (size_t i = 0; i < ctx.chips.size(); i++) {
    if (i) chips += ",";
    chips += std::to_string(ctx.chips[i].first);
  }
  out += "        \"env\": [\n";
  out += "          \"TPU_VISIBLE_CHIPS=" + chips + "\",\n";
  out += "          \"TPU_CHIPS_PER_PROCESS_BOUNDS="
         + bounds_for(ctx.chips.size()) + "\",\n";
  out += "          \"TPU_PROCESS_BOUNDS=1,1,1\",\n";
  out += "          \"TPU_SKIP_MDS_QUERY=1\",\n";
  out += "          \"JAX_PLATFORMS=tpu\"\n";
  out += "        ]";
  if (!ctx.libtpu.empty()) {
    out += ",\n        \"mounts\": [\n";
    out += "          {\"hostPath\": \"" + json_escape(ctx.libtpu)
           + "\", \"containerPath\": \"/usr/lib/libtpu.so\", "
             "\"options\": [\"ro\", \"rbind\"]}\n";
    out += "        ]\n";
  } else {
    out += "\n";
  }
  out += "      }\n";
  out += "    }\n";
  out += "  ]\n";
  out += "}\n";
  return out;
}

}  // namespace

int main(int argc, char** argv) {
  Ctx ctx;
  std::string out_path;
  for (int i = 1; i < argc - 1; i++) {
    if (strcmp(argv[i], "--dev-root") == 0) ctx.dev_root = argv[++i];
    else if (strcmp(argv[i], "--libtpu") == 0) ctx.libtpu = argv[++i];
    else if (strcmp(argv[i], "--out") == 0) out_path = argv[++i];
  }

  // chips: /dev/accel<N> (TPU VM runtime), numerically keyed by suffix
  for (const auto& name : list_dir(ctx.dev_root)) {
    if (name.rfind("accel", 0) == 0 && name.size() > 5 &&
        name.find_first_not_of("0123456789", 5) == std::string::npos)
      ctx.chips.emplace_back(atoi(name.c_str() + 5),
                             ctx.dev_root + "/" + name);
  }
  std::sort(ctx.chips.begin(), ctx.chips.end());
  // vfio groups (v5p+ runtimes expose chips through vfio)
  std::string vfio_dir = ctx.dev_root + "/vfio";
  for (const auto& name : list_dir(vfio_dir))
    ctx.vfio.push_back(vfio_dir + "/" + name);
  if (ctx.chips.empty() && !ctx.vfio.empty()) {
    // vfio-only host (same fallback as tpu_manager._inventory): the vfio
    // groups ARE the chips
    int i = 0;
    for (const auto& name : list_dir(vfio_dir))
      if (name != "vfio")
        ctx.chips.emplace_back(i++, vfio_dir + "/" + name);
  }
  if (ctx.chips.empty()) {
    fprintf(stderr, "t9cdi: no TPU devices under %s — refusing to write "
                    "an empty spec\n", ctx.dev_root.c_str());
    return 2;
  }

  if (ctx.libtpu.empty()) {
    for (const char* cand :
         {"/usr/lib/libtpu.so", "/usr/local/lib/libtpu.so",
          "/lib/libtpu.so"}) {
      if (exists(cand)) {
        ctx.libtpu = cand;
        break;
      }
    }
  }

  std::string spec = emit(ctx);
  if (out_path.empty()) {
    fwrite(spec.data(), 1, spec.size(), stdout);
  } else {
    FILE* f = fopen(out_path.c_str(), "w");
    if (f == nullptr) {
      perror("t9cdi: open --out");
      return 111;
    }
    size_t wrote = fwrite(spec.data(), 1, spec.size(), f);
    if (wrote != spec.size() || fclose(f) != 0) {
      perror("t9cdi: write --out");
      unlink(out_path.c_str());   // never leave a truncated spec behind
      return 111;
    }
  }
  fprintf(stderr, "t9cdi: %zu chips, %zu vfio groups, libtpu=%s\n",
          ctx.chips.size(), ctx.vfio.size(),
          ctx.libtpu.empty() ? "(none)" : ctx.libtpu.c_str());
  return 0;
}
